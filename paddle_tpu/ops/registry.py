"""Op registry: op type → XLA lowering rule.

The reference registers each op natively with C++ kernels per (place, dtype,
layout, library) (``paddle/fluid/framework/op_registry.h:197,237``), separate
``InferShape`` functions, and hand-written grad-op makers
(``grad_op_desc_maker.h:36``).  TPU-native, one registered jax lowering
function per op subsumes all three:

* **kernels** — the lowering *is* the kernel; XLA compiles/fuses it for the
  actual device, so there is no per-place kernel table;
* **InferShape** — derived with ``jax.eval_shape`` over the lowering
  (see :func:`infer_shapes`);
* **grad ops** — a generic ``<type>_grad`` lowering is derived with
  ``jax.vjp`` over the forward lowering (:func:`_make_generic_grad_def`).
  Because the Executor lowers the whole block into one jaxpr, XLA CSEs the
  forward recomputation inside the vjp against the original forward ops
  **where the forward is XLA ops**, so there the default grad costs no
  extra FLOPs.  A Mosaic kernel is a custom call XLA merges with nothing:
  re-derived, its forward runs twice a step.  An op whose site can route
  to such a kernel says so (``register_op(kernel_residuals=...)``), and
  where the same lowering call also holds the grad twin the Executor
  takes that forward once, through ``jax.vjp`` (:func:`call_op_keeping_vjp`),
  and hands the grad op the kept residuals (:class:`KeptForward`) in
  place of a second forward.  Inside a ``recompute_block`` region the
  grad is one ``jax.vjp`` over the whole region run again, which is what
  the region is for; but a kernel site's lowering may keep what its
  forward kernel computed and its backward kernels read across the
  region (:class:`RegionKept`, ``ctx.region``), and the re-run then runs
  no forward kernel there.  Ops can still register a hand-written
  ``<type>_grad`` where a different formula is preferable.

This mirrors the precedent the reference itself set for graph-compiler
backends: the nGraph bridge's per-op builders (``operators/ngraph/ops/*.h``,
``ngraph_engine.cc:474``), generalized to every op.
"""

import functools

import numpy as np

__all__ = [
    "register_op",
    "get_op_def",
    "has_op",
    "OpDef",
    "OpNotRegistered",
    "LoweringContext",
    "call_op",
    "call_op_keeping_vjp",
    "routes_to_kernel",
    "KeptForward",
    "RegionKept",
    "infer_shapes",
    "infer_output_structs",
    "EMPTY_VAR_NAME",
]

EMPTY_VAR_NAME = "@EMPTY@"

_OP_REGISTRY = {}

_SHAPE_SENTINELS = (100003, 100019, 100043, 100057, 100069, 100103, 100109)


class OpNotRegistered(KeyError):
    pass


def _parse_slots(slots):
    """'X' plain, 'X*' duplicable (list-valued slot)."""
    out = []
    for s in slots or []:
        if s.endswith("*"):
            out.append((s[:-1], True))
        else:
            out.append((s, False))
    return out


def _kwarg_name(slot):
    return slot.replace("@GRAD", "_grad").replace("@", "_")


class OpDef:
    def __init__(self, type, fn, inputs, outputs, no_grad=False,
                 infer_shape=None, grad_maker=None, stateful_outputs=(),
                 kernel_residuals=None):
        self.type = type
        self.fn = fn
        self.inputs = _parse_slots(inputs)  # [(slot, duplicable)]
        self.outputs = _parse_slots(outputs)
        self.no_grad = no_grad
        self.custom_infer_shape = infer_shape
        # custom grad maker: fn(op, block, out_grads: {slot: [names]},
        #   in_grads: {slot: [names]}) -> list of op-desc dicts
        self.grad_maker = grad_maker
        # output slots that are state (e.g. batch_norm running stats) —
        # excluded from differentiation paths
        self.stateful_outputs = set(stateful_outputs)
        # ``fn(ctx, attrs, **slots) -> bool``: does this site route to a
        # Mosaic kernel whose custom_vjp forward rule saves residuals for
        # its backward kernels?  (None: the op holds no such kernel.)
        self.kernel_residuals = kernel_residuals
        # on a generic ``<type>_grad`` def: the forward def it derives from
        self.fwd_def = None

    @property
    def input_slot_names(self):
        return [s for s, _ in self.inputs]

    @property
    def output_slot_names(self):
        return [s for s, _ in self.outputs]


def register_op(type, inputs, outputs, no_grad=False, infer_shape=None,
                grad_maker=None, stateful_outputs=(), kernel_residuals=None):
    """Decorator: register `fn(ctx, attrs, **slots)` as the lowering of `type`.

    Slot kwargs are arrays (or lists of arrays for duplicable slots, or None
    for absent optional slots).  Return value: a single array (one output
    slot), a tuple in declared output order, or a dict slot→array/list.
    ``kernel_residuals``: a predicate of the lowering's own signature,
    true where the site routes to a Mosaic kernel with saved residuals
    (module docstring: the forward is then taken once for both ops).
    """

    def deco(fn):
        _OP_REGISTRY[type] = OpDef(
            type, fn, inputs, outputs, no_grad=no_grad,
            infer_shape=infer_shape, grad_maker=grad_maker,
            stateful_outputs=stateful_outputs,
            kernel_residuals=kernel_residuals,
        )
        return fn

    return deco


def has_op(type):
    if type in _OP_REGISTRY:
        return True
    if type.endswith("_grad") and type[: -len("_grad")] in _OP_REGISTRY:
        return True
    return False


def get_op_def(type):
    d = _OP_REGISTRY.get(type)
    if d is not None:
        return d
    if type.endswith("_grad"):
        base = _OP_REGISTRY.get(type[: -len("_grad")])
        if base is not None:
            d = _make_generic_grad_def(base)
            _OP_REGISTRY[type] = d
            return d
    raise OpNotRegistered(type)


class LoweringContext:
    """Per-lowering state threaded through op fns.

    RNG: keys are derived deterministically from (step key, op id, draw index)
    so that a grad op recomputing its forward (vjp) draws identical randomness
    — which both makes dropout-style grads correct and lets XLA CSE the
    recompute against the original forward (XLA ops only; a Mosaic kernel's
    forward is kept instead, see the module docstring).
    """

    def __init__(self, base_key=None, mode="train"):
        self.base_key = base_key
        self.mode = mode
        self._op_id = 0
        self._rng_count = 0
        # hook for control-flow ops to lower sub-blocks; set by the executor
        self.lower_sub_block = None
        self.scope = None
        # unbounded-while support (two-pass, reference while_op.cc:189):
        # probing=True makes the `while` op run a host-level Python loop on
        # concrete values recording iteration counts into trip_counts
        # {sub_block_idx: n}; the jit trace then reads the counts as static
        # scan lengths for while_grad
        self.probing = False
        self.trip_counts = None
        # resilience fault injection: optional (name, value) -> value hook
        # applied to every op output at trace time (executor sets it when
        # a PADDLE_TPU_FAULT_SPEC names value faults; None = zero cost)
        self.fault_value_hook = None
        # {forward op id: (op type, "reused" | "recomputed" |
        # "kept_across_region", bytes kept across a region)}, filled at
        # trace time for the grad ops of Mosaic kernel sites
        # (:meth:`note_residual_site`) when the caller sets a dict;
        # None: no note
        self.residual_sites = None
        # the :class:`RegionKept` of the recompute region whose sub-block
        # is being lowered (``ops/control_flow.py``), else None
        self.region = None
        # the ``jax.named_scope`` of the op being lowered, set by the
        # Executor (``_run_ops_into_env``): ``pd<index>_<tag>``
        self.op_scope = "pd0_op"

    def part_scope(self, part):
        """The scope that names a part of the op being lowered for a
        device trace: ``<the op's own scope>.<part>``, inside the op's."""
        import jax

        return jax.named_scope("%s.%s" % (self.op_scope, part))

    def set_op(self, op_id):
        self._op_id = op_id
        self._rng_count = 0

    @property
    def op_id(self):
        """The id of the op being lowered (its forward twin's, in a grad
        op): what its RNG draws and its site's notes are keyed by."""
        return self._op_id

    def note_residual_site(self, op_type, path, kept_bytes=0):
        """Note what the backward of the kernel site being lowered took
        of its forward (``residual_sites``)."""
        if self.residual_sites is not None:
            self.residual_sites[self._op_id] = (op_type, path, kept_bytes)

    def rng(self):
        import jax

        key = self.base_key
        if key is None:
            key = jax.random.key(0)
        k = jax.random.fold_in(jax.random.fold_in(key, self._op_id), self._rng_count)
        self._rng_count += 1
        return k


def _normalize_result(opdef, res):
    """Normalize an op fn's return value to {slot: [values]}."""
    if isinstance(res, dict):
        named = res
    elif isinstance(res, tuple):
        named = {s: v for (s, _), v in zip(opdef.outputs, res)}
    else:
        slot = opdef.outputs[0][0]
        named = {slot: res}
    out = {}
    for slot, dup in opdef.outputs:
        if slot not in named or named[slot] is None:
            continue
        v = named[slot]
        out[slot] = list(v) if isinstance(v, (list, tuple)) else [v]
    return out


def _slot_kwargs(opdef, ins):
    kwargs = {}
    for slot, dup in opdef.inputs:
        vals = ins.get(slot) or []
        if dup:
            kwargs[_kwarg_name(slot)] = [v for v in vals]
        else:
            kwargs[_kwarg_name(slot)] = vals[0] if vals else None
    return kwargs


def call_op(opdef, ctx, ins, attrs, op_id=0, kept=None):
    """Invoke an op lowering. `ins`: {slot: [value-or-None]}.  ``kept``
    (generic grad defs only): the forward twin's :class:`KeptForward`."""
    ctx.set_op(op_id)
    kwargs = _slot_kwargs(opdef, ins)
    if kept is not None:
        kwargs["_kept"] = kept
    res = opdef.fn(ctx, dict(attrs), **kwargs)
    return _normalize_result(opdef, res)


def routes_to_kernel(opdef, ctx, ins, attrs):
    """Would lowering this site of ``opdef`` (or of the forward def a
    generic grad def derives from) run a Mosaic kernel with saved
    residuals?  The op's own ``kernel_residuals`` predicate decides."""
    fwd_def = opdef.fwd_def or opdef
    if fwd_def.kernel_residuals is None:
        return False
    return bool(fwd_def.kernel_residuals(ctx, dict(attrs),
                                         **_slot_kwargs(fwd_def, ins)))


class KeptForward:
    """One forward op's ``jax.vjp``, kept for its grad twin in the same
    lowering call: the values the forward saw (``ins``), its outputs
    (``primal``) and the pullback over the residuals its kernel saved."""

    __slots__ = ("ins", "primal", "vjp_fn")

    def __init__(self, ins, primal, vjp_fn):
        self.ins, self.primal, self.vjp_fn = ins, primal, vjp_fn

    def saw(self, ins):
        """Are the grad op's forward-slot values the very objects the
        forward op saw?  (A name overwritten in between: not.)"""
        for slot, vals in self.ins.items():
            theirs = ins.get(slot) or []
            if len(theirs) != len(vals) or any(
                    a is not b for a, b in zip(vals, theirs)):
                return False
        return True


class RegionKept:
    """What the Mosaic kernel sites of one recompute region keep of its
    forward run for its grad op's re-run: ``values[site op id]``, arrays.
    The forward ``recompute_block`` fills it where its grad twin is in
    the same lowering call (``rerun`` false), the grad op reads it
    (``rerun`` true; empty where the forward was lowered elsewhere).
    What to keep is the site's decision: only what a forward kernel
    computed and its backward kernels read, never an activation."""

    __slots__ = ("values", "rerun")

    def __init__(self, values=None):
        self.rerun = values is not None
        self.values = {} if values is None else values


def _fwd_slot_values(fwd_def, kwargs):
    """The forward's ``{slot: [values]}`` as its vjp differentiates it
    (absent optional slots left out), from slot kwargs."""
    fwd_in = {}
    for slot, dup in fwd_def.inputs:
        v = kwargs.get(_kwarg_name(slot))
        if v is None:
            continue
        fwd_in[slot] = list(v) if dup else [v]
    return fwd_in


def _forward_vjp(fwd_def, ctx, fwd_in, attrs, op_id):
    """``jax.vjp`` of a forward lowering over its ``{slot: [values]}``:
    ``(outs, pullback)``."""
    import jax

    def f(fin):
        return call_op(fwd_def, ctx, fin, attrs, op_id=op_id)

    return jax.vjp(f, fwd_in)


def call_op_keeping_vjp(opdef, ctx, ins, attrs, op_id=0):
    """:func:`call_op` of a forward op through ``jax.vjp``, exactly as its
    generic grad twin would re-derive it: ``(outs, KeptForward)``."""
    primal, vjp_fn = _forward_vjp(
        opdef, ctx, _fwd_slot_values(opdef, _slot_kwargs(opdef, ins)),
        attrs, op_id)
    return primal, KeptForward({s: list(ins.get(s) or [])
                                for s, _ in opdef.inputs}, primal, vjp_fn)


# ---------------------------------------------------------------------------
# Generic grad op derivation via jax.vjp
# ---------------------------------------------------------------------------

def _make_generic_grad_def(fwd_def):
    import jax
    import jax.numpy as jnp

    grad_inputs = []
    for slot, dup in fwd_def.inputs:
        grad_inputs.append(slot + ("*" if dup else ""))
    for slot, dup in fwd_def.outputs:
        grad_inputs.append(slot + ("*" if dup else ""))
        grad_inputs.append(slot + "@GRAD" + ("*" if dup else ""))
    grad_outputs = [
        slot + "@GRAD" + ("*" if dup else "") for slot, dup in fwd_def.inputs
    ]

    def grad_fn(ctx, attrs, _kept=None, **kwargs):
        # reconstruct raw slot dicts from kwargs
        fwd_in = _fwd_slot_values(fwd_def, kwargs)
        out_grads = {}
        for slot, dup in fwd_def.outputs:
            g = kwargs.get(_kwarg_name(slot + "@GRAD"))
            if g is None:
                continue
            out_grads[slot] = list(g) if dup else [g]

        if _kept is not None:
            # the forward op already ran under jax.vjp in this lowering
            # call, on these very values: its residuals, no second forward
            primal, vjp_fn = _kept.primal, _kept.vjp_fn
        else:
            primal, vjp_fn = _forward_vjp(
                fwd_def, ctx, fwd_in, attrs,
                attrs.get("__fwd_op_id__", attrs.get("__op_id__", 0)))
        # build cotangents matching the primal pytree exactly
        cot = {}
        for slot, vals in primal.items():
            gs = out_grads.get(slot)
            lst = []
            for i, p in enumerate(vals):
                g = gs[i] if gs is not None and i < len(gs) and gs[i] is not None else None
                if g is None or slot in fwd_def.stateful_outputs:
                    g = jnp.zeros(jnp.shape(p), _cotangent_dtype(p))
                else:
                    g = g.astype(_cotangent_dtype(p))
                # under shard_map the primal may be varying over manual
                # mesh axes; a freshly built cotangent is replicated and
                # jax rejects the vma mismatch — promote it to match.
                missing = jax.typeof(p).vma - jax.typeof(g).vma
                if missing:
                    g = jax.lax.pcast(g, tuple(missing), to="varying")
                lst.append(g)
            cot[slot] = lst
        (gin,) = vjp_fn(cot)
        result = {}
        for slot, dup in fwd_def.inputs:
            if slot not in gin:
                continue
            vals = []
            for i, g in enumerate(gin[slot]):
                if g is None or g.dtype == jax.dtypes.float0:
                    # non-differentiable (int) input: emit zeros so the slot
                    # is well-formed if someone requested it anyway
                    p = fwd_in[slot][i]
                    g = jnp.zeros(jnp.shape(p), jnp.float32)
                vals.append(g)
            result[slot + "@GRAD"] = vals
        return result

    d = OpDef(
        fwd_def.type + "_grad", grad_fn, grad_inputs, grad_outputs, no_grad=True
    )
    d.fwd_def = fwd_def
    return d


def _cotangent_dtype(p):
    import jax.numpy as jnp

    d = jnp.result_type(p)
    if jnp.issubdtype(d, jnp.floating) or jnp.issubdtype(d, jnp.complexfloating):
        return d
    return jnp.float32


# ---------------------------------------------------------------------------
# Shape/dtype inference via jax.eval_shape
# ---------------------------------------------------------------------------

def _np_dtype_of(var):
    import jax.numpy as jnp

    if var.dtype == "bfloat16":
        return jnp.bfloat16
    return np.dtype(var.dtype)


def infer_shapes(op, block):
    """Infer output var shapes/dtypes for a freshly appended op by running
    jax.eval_shape over its lowering, with -1 dims replaced by sentinel
    primes (mapped back to -1 afterwards).  Static shapes here are
    graph-construction metadata only; execution re-traces with concrete feed
    shapes, so approximation is acceptable (the reference's InferShape has
    the same -1-propagation looseness, framework.py:985)."""
    opdef = get_op_def(op.type)

    if opdef.custom_infer_shape is not None:
        opdef.custom_infer_shape(op, block)
        return

    inferred = infer_output_structs(op, block)
    if inferred is None:
        return
    for n, (shape, dtype) in inferred.items():
        var = block._find_var_recursive(n)
        if var is None:
            continue
        var.shape = shape
        var.dtype = dtype


def infer_output_structs(op, block):
    """Non-mutating core of :func:`infer_shapes`: eval_shape the op's
    lowering against the recorded input metadata and return
    ``{out_var_name: (shape_with_-1_dims, dtype_str)}``, or None when the
    op is not inferable this way (custom InferShape, un-inferable inputs,
    sentinel arithmetic broke the trace).  The verifier diffs this against
    recorded Variable metadata to catch drift introduced by pass rewrites
    without touching the graph."""
    import jax

    opdef = get_op_def(op.type)
    if opdef.custom_infer_shape is not None:
        return None

    ins = {}
    used_sentinel = False
    for slot, names in op.inputs.items():
        vals = []
        for n in names:
            if n == EMPTY_VAR_NAME:
                vals.append(None)
                continue
            var = block._find_var_recursive(n)
            if var is None or var.shape is None:
                return None  # cannot infer
            shape = []
            for i, d in enumerate(var.shape):
                if d is None or d < 0:
                    shape.append(_SHAPE_SENTINELS[i % len(_SHAPE_SENTINELS)])
                    used_sentinel = True
                else:
                    shape.append(int(d))
            vals.append(jax.ShapeDtypeStruct(tuple(shape), _np_dtype_of(var)))
        ins[slot] = vals

    ctx = LoweringContext(base_key=None, mode="infer")

    def f(ins_):
        return call_op(opdef, ctx, ins_, op.attrs, op_id=op.attrs.get("__op_id__", 0))

    try:
        out_structs = jax.eval_shape(f, ins)
    except Exception:
        if used_sentinel:
            return None  # sentinel arithmetic broke the trace
        raise

    sent = set(_SHAPE_SENTINELS)
    out = {}
    for slot, names in op.outputs.items():
        structs = out_structs.get(slot)
        if structs is None:
            continue
        for n, s in zip(names, structs):
            if s is None or n == EMPTY_VAR_NAME:
                continue
            shape = tuple(-1 if d in sent else int(d) for d in s.shape)
            dtype = ("bfloat16" if s.dtype == _np_dtype_of_bf16()
                     else np.dtype(s.dtype).name)
            out[n] = (shape, dtype)
    return out


@functools.lru_cache(maxsize=1)
def _np_dtype_of_bf16():
    import jax.numpy as jnp

    return jnp.bfloat16
