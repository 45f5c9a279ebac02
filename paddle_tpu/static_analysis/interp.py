"""Abstract interpretation over the Program IR.

The transpilers and parallel passes rewrite distribution INTO the same
``Program`` the executor runs, so the facts that matter for a
distributed run — what shape/dtype every value has, which values are
sharded over which mesh axis, which are replicated on every worker —
are statically derivable before a single device cycle is spent.  This
module walks the Program in execution order (descending
``attrs["sub_block"]`` bodies like the def-use walker) propagating an
:class:`AbstractVal` per var:

* **shape** — the recorded static shape with ``-1`` (batch) dims
  resolved against a configurable assumed batch size, so downstream
  consumers (the cost model) see concrete element counts;
* **dtype** — recorded dtype string;
* **persistable** — scope-resident across steps (params, optimizer
  state);
* **sharding** — a small lattice (BOTTOM < REPLICATED | SHARDED <
  UNKNOWN) seeded from transpiler/parallel annotations
  (``Parameter.shard_spec``, ``_is_distributed`` row-sharding,
  ``program._num_trainers`` batch sharding of fed data vars) and
  propagated through ops by per-type transfer rules
  (:func:`register_transfer`, the ``register_check`` idiom).

The interpreter never executes a lowering: it reads the Variable
metadata the build-time ``jax.eval_shape`` inference recorded (the
``shape-dtype-drift`` check separately proves that metadata is still
consistent with the lowerings), which keeps ``analyze()`` cheap enough
to run in CI over every example program.
"""

import os

from .defuse import SUB_BLOCK_DESCENT_OPS, resolve_sub_block

__all__ = [
    "Sharding", "AbstractVal", "OpRecord", "InterpResult",
    "interpret_program", "register_transfer", "assumed_batch_size",
    "DATA_AXIS",
]

# mesh-axis naming convention: fed data vars of an N-trainer program are
# batch-sharded over this axis (parallel/__init__._make_mesh)
DATA_AXIS = "data"


def assumed_batch_size(default=1):
    """The batch size ``-1`` dims resolve to during analysis.  Static
    analysis needs concrete element counts for FLOP/byte totals; the env
    var ``PADDLE_TPU_ANALYZE_BATCH`` pins it (default 1 — every total
    then reads as "per example")."""
    val = os.environ.get("PADDLE_TPU_ANALYZE_BATCH", "").strip()
    if val:
        return max(1, int(val))
    return default


class Sharding:
    """One point of the sharding/replication lattice.

    ``BOTTOM`` (no information yet) < ``REPLICATED`` / ``SHARDED(axis,
    dim, parts)`` < ``UNKNOWN`` (conflicting facts).  ``join`` moves up
    the lattice; transfer rules move values sideways (a collective
    turns SHARDED into REPLICATED, an explicit reshard changes the
    axis/dim)."""

    BOTTOM = "bottom"
    REPLICATED = "replicated"
    SHARDED = "sharded"
    UNKNOWN = "unknown"

    __slots__ = ("kind", "axis", "dim", "parts")

    def __init__(self, kind, axis=None, dim=None, parts=1):
        self.kind = kind
        self.axis = axis
        self.dim = dim
        self.parts = int(parts or 1)

    @classmethod
    def bottom(cls):
        return cls(cls.BOTTOM)

    @classmethod
    def replicated(cls):
        return cls(cls.REPLICATED)

    @classmethod
    def sharded(cls, axis, dim, parts):
        if parts <= 1:
            return cls.replicated()
        return cls(cls.SHARDED, axis=axis, dim=dim, parts=parts)

    @classmethod
    def unknown(cls):
        return cls(cls.UNKNOWN)

    @property
    def is_sharded(self):
        return self.kind == self.SHARDED

    def __eq__(self, other):
        return (isinstance(other, Sharding) and self.kind == other.kind
                and self.axis == other.axis and self.dim == other.dim
                and self.parts == other.parts)

    def __hash__(self):
        return hash((self.kind, self.axis, self.dim, self.parts))

    def join(self, other):
        if self == other:
            return self
        if self.kind == self.BOTTOM:
            return other
        if other.kind == self.BOTTOM:
            return self
        return Sharding.unknown()

    def __repr__(self):
        if self.kind == self.SHARDED:
            return "sharded(%s, dim=%s, parts=%d)" % (
                self.axis, self.dim, self.parts)
        return self.kind


class AbstractVal:
    """Everything the analyzer statically knows about one var."""

    __slots__ = ("name", "shape", "dtype", "persistable", "sharding")

    def __init__(self, name, shape, dtype, persistable=False,
                 sharding=None):
        self.name = name
        self.shape = tuple(shape) if shape is not None else None
        self.dtype = str(dtype) if dtype is not None else None
        self.persistable = bool(persistable)
        self.sharding = sharding or Sharding.bottom()

    @property
    def numel(self):
        """Global element count (None when the shape is unknown)."""
        if self.shape is None:
            return None
        n = 1
        for d in self.shape:
            n *= max(int(d), 1)
        return n

    @property
    def local_numel(self):
        """Per-worker element count: global / parts when sharded."""
        n = self.numel
        if n is None:
            return None
        if self.sharding.is_sharded:
            return max(1, n // self.sharding.parts)
        return n

    def __repr__(self):
        return "AbstractVal(%s: %s %s%s, %r)" % (
            self.name, self.shape, self.dtype,
            " persistable" if self.persistable else "", self.sharding)


class OpRecord:
    """One interpreted op: coordinates + resolved input/output values,
    in walk (execution) order — the unit the cost model consumes."""

    __slots__ = ("index", "block_idx", "op_idx", "op", "ins", "outs")

    def __init__(self, index, block_idx, op_idx, op, ins, outs):
        self.index = index
        self.block_idx = block_idx
        self.op_idx = op_idx
        self.op = op
        self.ins = ins      # [AbstractVal] in input_arg_names order
        self.outs = outs    # [AbstractVal] in output_arg_names order

    def __repr__(self):
        return "OpRecord(%d: block %d op %d %s)" % (
            self.index, self.block_idx, self.op_idx, self.op.type)


class InterpResult:
    """Final abstract environment + per-op trace.

    ``env``:      {var name: AbstractVal} after the walk
    ``records``:  [OpRecord] in execution order
    ``nranks``:   worker count the sharding lattice was seeded with
    ``batch_size``: what -1 dims resolved to
    """

    def __init__(self, program, env, records, nranks, batch_size):
        self.program = program
        self.env = env
        self.records = records
        self.nranks = nranks
        self.batch_size = batch_size

    def val(self, name):
        return self.env.get(name)

    def sharded_vars(self):
        return {n: v for n, v in self.env.items()
                if v.sharding.is_sharded}

    def replicated_persistables(self):
        return {n: v for n, v in self.env.items()
                if v.persistable and not v.sharding.is_sharded}


# ---------------------------------------------------------------------------
# transfer rules
# ---------------------------------------------------------------------------

_TRANSFERS = {}


def register_transfer(op_type):
    """Register ``fn(op, in_vals, out_val) -> Sharding`` as the sharding
    transfer rule for ``op_type`` (``in_vals``: [AbstractVal];
    ``out_val``: the AbstractVal being produced, sharding not yet set).
    Later registration replaces earlier, like ``register_check``."""

    def deco(fn):
        _TRANSFERS[op_type] = fn
        return fn

    return deco


def _default_transfer(op, in_vals, out_val):
    """Join of the input shardings, with a shape guard: a sharded input
    propagates only when the output has the same global shape (the
    elementwise/unary case); shape-changing ops degrade to UNKNOWN
    rather than invent a wrong placement."""
    s = Sharding.bottom()
    for v in in_vals:
        s = s.join(v.sharding)
    if s.kind == Sharding.BOTTOM:
        return Sharding.replicated()
    if s.is_sharded:
        shaped = [v for v in in_vals if v.sharding.is_sharded]
        if any(v.shape != out_val.shape for v in shaped):
            return Sharding.unknown()
    return s


def _replicating_transfer(op, in_vals, out_val):
    return Sharding.replicated()


# collectives produce replicated values (allreduce/allgather/broadcast
# materialize the global value on every participant)
for _t in ("c_allreduce_sum", "c_allreduce_max", "c_allreduce_min",
           "c_allreduce_prod", "allreduce", "c_broadcast", "broadcast",
           "c_allgather", "fill_constant", "c_fused_allreduce_sum",
           "c_allreduce_quant", "c_allreduce_start", "c_allreduce_wait",
           "c_hier_reducescatter", "c_hier_allgather"):
    register_transfer(_t)(_replicating_transfer)


@register_transfer("fused_conv_bn_act")
def _fused_conv_bn_transfer(op, in_vals, out_val):
    # conv preserves the batch dim: a batch-sharded input stays
    # batch-sharded even though the spatial/channel shape changes (the
    # default rule would degrade the shape change to UNKNOWN).  Applies
    # to the rank-preserving Out ONLY — the [C]-shaped MeanOut/
    # VarianceOut running stats are replicated, and stamping them
    # sharded would report C/parts local elements for a full vector
    if in_vals and in_vals[0].sharding.is_sharded \
            and in_vals[0].sharding.dim == 0 \
            and out_val.shape is not None \
            and in_vals[0].shape is not None \
            and len(out_val.shape) == len(in_vals[0].shape) \
            and out_val.shape[0] == in_vals[0].shape[0]:
        return in_vals[0].sharding
    if out_val.shape is not None and len(out_val.shape) == 1:
        return Sharding.replicated()  # the running-stat outputs
    return _default_transfer(op, in_vals, out_val)


@register_transfer("c_reducescatter")
def _reducescatter_transfer(op, in_vals, out_val):
    parts = max((v.sharding.parts for v in in_vals
                 if v.sharding.is_sharded), default=1)
    return Sharding.sharded(DATA_AXIS, 0, parts) if parts > 1 \
        else Sharding.unknown()


@register_transfer("kv_cache_write")
@register_transfer("kv_cache_prefill")
@register_transfer("paged_kv_cache_write")
@register_transfer("paged_kv_cache_prefill")
def _kv_cache_transfer(op, in_vals, out_val):
    # the output IS the cache (ring-buffer update): it keeps the cache's
    # placement.  The default join would degrade to UNKNOWN whenever the
    # [B,H,D] step row is sharded (different shape from the cache)
    if in_vals:
        return in_vals[0].sharding
    return _default_transfer(op, in_vals, out_val)


@register_transfer("flash_decode_attention")
@register_transfer("paged_flash_decode_attention")
def _flash_decode_transfer(op, in_vals, out_val):
    # out [B,H,D] follows the query row's placement (batch-sharded
    # serving slots stay batch-sharded); the cache inputs don't shard
    # the output — each worker reads its own slots' cache blocks
    if in_vals and in_vals[0].sharding.is_sharded:
        return in_vals[0].sharding
    return Sharding.replicated()


@register_transfer("top_k_sampling")
@register_transfer("top_p_sampling")
def _sampling_transfer(op, in_vals, out_val):
    # ids [B] from logits [B,V]: batch sharding survives the vocab-dim
    # reduction; a vocab-sharded input would need a cross-worker argmax,
    # which the lowering doesn't do — flag UNKNOWN so the analyzer warns
    if in_vals and in_vals[0].sharding.is_sharded:
        s = in_vals[0].sharding
        return s if s.dim == 0 else Sharding.unknown()
    return Sharding.replicated()


@register_transfer("all_to_all")
def _all_to_all_transfer(op, in_vals, out_val):
    # a reshard: stays sharded over the same axis, the sharded tensor
    # dim moves from split_axis to concat_axis
    for v in in_vals:
        if v.sharding.is_sharded:
            return Sharding.sharded(
                v.sharding.axis, int(op.attrs.get("concat_axis", 0)),
                v.sharding.parts)
    return _default_transfer(op, in_vals, out_val)


def _transfer(op, in_vals, out_val):
    fn = _TRANSFERS.get(op.type, _default_transfer)
    return fn(op, in_vals, out_val)


# ---------------------------------------------------------------------------
# the walk
# ---------------------------------------------------------------------------

def _resolve_shape(shape, batch_size):
    if shape is None:
        return None
    return tuple(batch_size if (d is None or int(d) < 0) else int(d)
                 for d in shape)


def _seed_sharding(var, nranks, data_parallel=True):
    """Initial lattice point from build/transpiler annotations."""
    if nranks <= 1:
        return Sharding.replicated()
    spec = getattr(var, "shard_spec", None)
    if spec:
        # shard_spec: {tensor_dim: mesh_axis} or (axis names per dim)
        if isinstance(spec, dict):
            for dim, axis in spec.items():
                if axis:
                    return Sharding.sharded(axis, int(dim), nranks)
        else:
            for dim, axis in enumerate(spec):
                if axis:
                    return Sharding.sharded(axis, dim, nranks)
    if getattr(var, "_is_distributed", False) or getattr(
            var, "is_distributed", False):
        return Sharding.sharded(DATA_AXIS, 0, nranks)  # row-sharded table
    if var.is_data and data_parallel:
        # N-trainer programs shard every feed's batch dim over the data
        # axis (parallel/__init__.SPMDRunner); pipeline-stage worker
        # programs (nranks = #stages) feed each stage its LOCAL batch
        return Sharding.sharded(DATA_AXIS, 0, nranks)
    return Sharding.replicated()


def interpret_program(program, nranks=None, batch_size=None,
                      shard_overrides=None):
    """Walk ``program`` and return an :class:`InterpResult`.

    ``nranks``: worker count for the sharding lattice (default: the
    ``program._num_trainers`` the transpiler recorded, else 1).
    ``batch_size``: what ``-1`` dims resolve to (default
    :func:`assumed_batch_size`).
    ``shard_overrides``: ``{var name: Sharding}`` candidate seeding —
    pins the named vars to the given lattice points for the whole walk
    (seed AND after every producing op), overriding both the recorded
    annotations and the transfer rules.  This is how the
    auto-parallelism planner prices hypothetical per-layer shard specs
    (e.g. ZeRO-sharded optimizer state) without mutating the program.
    """
    if nranks is None:
        nranks = int(getattr(program, "_num_trainers", 1) or 1)
    if batch_size is None:
        batch_size = assumed_batch_size()
    # pipeline-stage workers feed each stage its LOCAL batch (feeds
    # replicated) — EXCEPT hierarchical pipeline x dp stages, which
    # carry _num_trainers = dp subgroup size and shard their feeds over
    # it like any data-parallel program
    data_parallel = (getattr(program, "_pipeline_stage", None) is None
                     or int(getattr(program, "_num_trainers", 0)
                            or 0) > 1)
    shard_overrides = shard_overrides or {}

    env = {}
    records = []
    visited_blocks = set()

    def lookup(name, block):
        v = env.get(name)
        if v is not None:
            return v
        var = block._find_var_recursive(name)
        if var is None:
            av = AbstractVal(name, None, None)
        else:
            av = AbstractVal(
                name, _resolve_shape(var.shape, batch_size), var.dtype,
                persistable=var.persistable,
                sharding=_seed_sharding(var, nranks, data_parallel))
        if name in shard_overrides:
            av.sharding = shard_overrides[name]
        env[name] = av
        return av

    def walk(block):
        if block.idx in visited_blocks:
            return
        visited_blocks.add(block.idx)
        for op_idx, op in enumerate(block.ops):
            in_vals = [lookup(n, block) for n in op.input_arg_names]
            if op.type in SUB_BLOCK_DESCENT_OPS:
                inner = resolve_sub_block(program, op,
                                          host_block_idx=block.idx)
                if inner is not None:
                    walk(inner)
            out_vals = []
            for n in op.output_arg_names:
                var = block._find_var_recursive(n)
                av = AbstractVal(
                    n,
                    _resolve_shape(
                        var.shape if var is not None else None,
                        batch_size),
                    var.dtype if var is not None else None,
                    persistable=bool(var is not None and var.persistable))
                av.sharding = shard_overrides.get(
                    n) or _transfer(op, in_vals, av)
                env[n] = av
                out_vals.append(av)
            records.append(OpRecord(len(records), block.idx, op_idx, op,
                                    in_vals, out_vals))

    walk(program.global_block())
    # vars no op references (freshly created params, orphaned temps)
    # still exist in the scope — seed them so persistable-memory and
    # sharding summaries cover the whole program, not just the op graph
    for block in program.blocks:
        for name in block.vars:
            if name not in env:
                lookup(name, block)
    return InterpResult(program, env, records, nranks, batch_size)
