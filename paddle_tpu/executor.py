"""Executor: Program → jaxpr lowering + jit cache.

The reference Executor interprets a block op-by-op against a mutable Scope
(``paddle/fluid/framework/executor.cc:416`` hot loop, kernel dispatch at
``operator.cc:881``).  On TPU that design would bounce every intermediate
through HBM and defeat XLA fusion, so this Executor instead:

1. analyzes the block once: feeds, fetches, which scope (persistable) vars
   are read, which are written (SSA-ification of the mutable-Scope program);
2. lowers the whole block into ONE pure jax function
   ``f(feeds, mutable_params, ro_params, rng_key) -> (fetches, new_params)``;
3. ``jax.jit``-compiles it with the mutable param buffers donated (the
   functional analogue of the reference's in-place param updates + its
   memory-reuse passes), and caches the compilation keyed on
   (program version, feed shapes/dtypes, fetch names) — the same shape-keyed
   engine cache the reference's nGraph bridge uses
   (``operators/ngraph/ngraph_engine.cc:515``).

Feed/fetch become function arguments/results instead of `feed`/`fetch` ops
writing into scope slots (``executor.cc:254-325``); `feed`/`fetch` ops that
exist in serialized programs are recognized and skipped.
"""

import collections
import contextlib
import threading
import time as _time

import numpy as np

from . import core
from . import pipeline as _pipeline
from .observability import runtime as _obs
from .observability import tracing as _tr
from .framework import Program, default_main_program, Variable
from .ops import registry as op_registry
from .ops.pallas.flash_attention import noting_blocks
from .ops.registry import EMPTY_VAR_NAME
from .pipeline import FetchHandle

__all__ = ["Executor", "Scope", "global_scope", "scope_guard",
           "FetchHandle"]


class _ScopeTensor:
    """LoDTensor-flavored view over a scope entry (reference
    ``pybind.cc:202`` Tensor bindings): supports np.array(t), t.set(arr),
    t.shape()."""

    def __init__(self, scope, name):
        self._scope = scope
        self._name = name

    def set(self, array, place=None):
        import jax.numpy as jnp

        self._scope.vars[self._name] = jnp.asarray(array)

    def __array__(self, dtype=None):
        a = np.asarray(self._scope.vars[self._name])
        return a.astype(dtype) if dtype is not None else a

    def shape(self):
        return list(np.shape(self._scope.vars[self._name]))

    def set_lod(self, lod):
        self._scope.lod[self._name] = lod

    def lod(self):
        return self._scope.lod.get(self._name, [])


class _ScopeVar:
    def __init__(self, scope, name):
        self._scope = scope
        self._name = name

    def get_tensor(self):
        return _ScopeTensor(self._scope, self._name)

    def name(self):
        return self._name


def rng_key(seed):
    """Base PRNG key.  On TPU the default is the hardware-accelerated
    ``rbg`` generator — threefry bit generation is pure VPU arithmetic and
    costs real step time in dropout-heavy models (~25% of a BERT-base
    train step at bs64); override with PADDLE_TPU_RNG_IMPL=threefry2x32
    (alias: threefry) for bit-exact cross-platform draws.  Note the
    default therefore differs between CPU (threefry2x32) and TPU/GPU
    (rbg): fixed-seed runs are NOT reproducible across backends unless
    the env var pins one impl."""
    import os

    import jax

    impl = os.environ.get("PADDLE_TPU_RNG_IMPL")
    if impl == "threefry":
        impl = "threefry2x32"
    if impl is None:
        from .ops.pallas import device_platform

        impl = "threefry2x32" if device_platform() == "cpu" else "rbg"
    return jax.random.key(int(seed), impl=impl)


class Scope:
    """name → device array map (reference ``framework/scope.h:45``; the
    parent-chain lexical lookup is preserved for local scopes)."""

    def __init__(self, parent=None):
        self.vars = {}
        self.lod = {}
        self.parent = parent
        self._kids = []

    def var(self, name):
        if name not in self.vars and self.find_var(name) is None:
            self.vars[name] = None
        return _ScopeVar(self._owner_of(name), name)

    def find_var(self, name):
        s = self
        while s is not None:
            if name in s.vars:
                return _ScopeVar(s, name)
            s = s.parent
        return None

    def _owner_of(self, name):
        s = self
        while s is not None:
            if name in s.vars:
                return s
            s = s.parent
        return self

    def new_scope(self):
        kid = Scope(parent=self)
        self._kids.append(kid)
        return kid

    def drop_kids(self):
        self._kids = []

    def local_var_names(self):
        return list(self.vars)

    # internal helpers
    def get(self, name):
        s = self
        while s is not None:
            if name in s.vars:
                return s.vars[name]
            s = s.parent
        return None

    def has(self, name):
        s = self
        while s is not None:
            if name in s.vars:
                return True
            s = s.parent
        return False

    def set(self, name, value):
        self._owner_of(name).vars[name] = value


_global_scope = Scope()


class _ScopeStack(threading.local):
    """PER-THREAD scope stack (latent hazard found by the ISSUE-10
    concurrency analyzer): the stack used to be one process-wide list,
    so two predictors serving from different threads interleaved their
    ``scope_guard`` push/pops — thread A's executor could resolve
    ``global_scope()`` to thread B's private scope and read (or donate)
    the other tenant's weights.  Each thread now gets its own stack
    rooted at the shared global scope; single-threaded behavior is
    unchanged, and the ``scope-overlap`` check proves the remaining
    (deliberate) sharing safe."""

    def __init__(self):
        self.frames = [_global_scope]


_scope_stack = _ScopeStack()


def global_scope():
    return _scope_stack.frames[-1]


@contextlib.contextmanager
def scope_guard(scope):
    _scope_stack.frames.append(scope)
    try:
        yield
    finally:
        _scope_stack.frames.pop()


def as_numpy(value):
    if isinstance(value, (list, tuple)):
        return [as_numpy(v) for v in value]
    return np.asarray(value)


def _finish_fetches(fetches, return_numpy, fetch_names=(),
                    state_names=(), step_info=None):
    """Fetch-return protocol shared by Executor.run and SPMDRunner.run.

    ``return_numpy=True``: ONE batched device→host sync issued after the
    whole step is dispatched (every D2H copy starts async, then gathers)
    — not one blocking ``np.asarray`` per fetch value.
    ``return_numpy=False``: lazy :class:`FetchHandle`\\ s — no sync at
    all until a handle is materialized, so a serving/training loop can
    keep many steps in flight and block once.

    A fetch value whose name is in ``state_names`` (the compiled
    block's read-write / fresh persistables) IS the scope array the
    next step's donation invalidates — exactly the
    ``donated-buffer-live-read`` hazard the concurrency analyzer flags.
    Lazy handles for those are detached with a device-side copy (async,
    no host sync) so a handle materialized after later steps dispatched
    still reads this step's value instead of a deleted buffer.

    ``step_info`` rides on the lazy handles (see :class:`FetchHandle`),
    so that materialising one tells the program its step is done."""
    if return_numpy:
        return _pipeline.host_values(fetches)
    out = []
    state = set(state_names)
    for i, v in enumerate(fetches):
        if (state and i < len(fetch_names)
                and fetch_names[i] in state
                and not isinstance(v, FetchHandle)):
            v = _pipeline.detach_device(v)
        out.append(v if isinstance(v, FetchHandle)
                   else FetchHandle(v, step_info))
    return out


def _register_compile_telemetry(compiled, program, feed_vals,
                                fetch_names):
    """Compile-time telemetry (shared by Executor and SPMDRunner):
    register the cost model's predictions with the drift monitor and
    install the compiled program's extracted collective schedule as
    per-ring launch/payload gauges.  Best-effort — static analysis must
    never fail a run — and skipped entirely under the kill switch."""
    from .observability.metrics import telemetry_enabled

    if not telemetry_enabled():
        return
    try:
        from .observability import drift as _drift

        batch = None
        for v in feed_vals.values():
            shape = getattr(v, "shape", None)
            if shape:
                batch = int(shape[0])
                break
        key = _drift.monitor().register_program(
            program, batch_size=batch, targets=fetch_names)
        compiled._drift_key = key
        if key is not None:
            from .static_analysis.distributed import \
                extract_collective_schedule

            _obs.set_collective_schedule(
                extract_collective_schedule(program, batch_size=batch),
                drift_key=key)
    except Exception:  # noqa: BLE001 - telemetry never breaks a run
        compiled._drift_key = None


# ops executed host-side by Executor.run, invisible to the jit path
# (feed/fetch are call arguments/results; save/load run via io_ops)
_HOST_SIDE_OPS = ("feed", "fetch", "save", "load", "save_combine",
                  "load_combine")

# extra feed carrying the resilience fault-injection gate vector —
# present only under an active PADDLE_TPU_FAULT_SPEC with value faults,
# so normal runs never pay for it.  (faults.py owns the name; safe to
# import at module level: resilience/ is stdlib-only at import time.)
from .resilience.faults import GATE_FEED as _FAULT_GATE_FEED


def _probe_trip_counts(block, feed_vals, scope, fetch_names):
    """Pass 1 of unbounded-while gradients (while_op.cc:189 parity):
    eagerly run the block's forward prefix on the concrete feed/scope
    values, counting iterations of every unbounded while (the `while` op
    lowering runs a host loop under ctx.probing).  Pass 2 traces the
    block with these counts as static masked-scan lengths; the jit cache
    keys on them, so a different trip count recompiles rather than
    reusing a too-short scan."""
    ext_reads, _, _ = _analyze_block(block, list(feed_vals), fetch_names)
    env = {n: scope.get(n) for n in ext_reads if scope.has(n)}
    env.update(feed_vals)
    ctx = op_registry.LoweringContext(base_key=rng_key(0), mode="train")
    ctx.probing = True
    ctx.trip_counts = {}
    prefix = []
    for op in block.ops:
        if op.type.endswith("_grad"):
            break  # grads follow every forward op; every while — incl.
            # those nested in cond/recurrent sub-blocks — has been
            # entered (and counted) by the forward prefix
        if op.type in _HOST_SIDE_OPS:
            continue
        prefix.append(op)
    _run_ops_into_env(block, env, ctx, ops=prefix)
    return ctx.trip_counts


def _is_training_program(program):
    """Does the global block train (grad/optimize ops present)?  Gates
    both the finite step-guard and value-fault injection: an eval or
    startup dispatch at the same step must neither engage the guard nor
    burn a value fault's firing budget."""
    for op in program.global_block().ops:
        if op.type.endswith("_grad") \
                or op.attrs.get("op_role") == "optimize":
            return True
    return False


def _has_unbounded_while_grad(program):
    """Any while_grad without max_trip_count, in ANY block (an unbounded
    while may sit inside a cond/recurrent sub-block)."""
    for block in program.blocks:
        for op in block.ops:
            if (op.type == "while_grad"
                    and not op.attrs.get("max_trip_count")):
                return True
    return False


def _analyze_block(block, feed_names, fetch_names):
    """SSA analysis: (external scope reads, written names, written persistables)."""
    defined = set(feed_names)
    ext_reads = []
    written = []
    for op in block.ops:
        if op.type in _HOST_SIDE_OPS:
            continue
        for n in op.input_arg_names:
            if n and n != EMPTY_VAR_NAME and n not in defined:
                if n not in ext_reads:
                    ext_reads.append(n)
        for n in op.output_arg_names:
            if n and n != EMPTY_VAR_NAME:
                defined.add(n)
                written.append(n)
    for n in fetch_names:
        if n not in defined and n not in ext_reads:
            ext_reads.append(n)
    persist_written = []
    for n in written:
        v = block._find_var_recursive(n)
        if v is not None and v.persistable and n not in persist_written:
            persist_written.append(n)
    return ext_reads, written, persist_written


# most recently constructed block — bench/profiling hook: its .jitted
# drives AOT cost_analysis (XLA's own FLOPs) without re-tracing state
_LAST_COMPILED_BLOCK = None


def _all_finite(values):
    """One scalar flag: every inexact value in `values` is NaN/Inf-free
    (the in-graph side of the resilience NaN step-guard)."""
    import jax.numpy as jnp

    flags = [jnp.all(jnp.isfinite(v)) for v in values
             if v is not None and hasattr(v, "dtype")
             and jnp.issubdtype(v.dtype, jnp.inexact)]
    if not flags:
        return jnp.asarray(True)
    out = flags[0]
    for f in flags[1:]:
        out = jnp.logical_and(out, f)
    return out


def _guard_select(finite, new, old):
    """Route a state update through the finite flag: a non-finite step
    keeps the old value bit-identically (dynamic-loss-scaling-style
    skip)."""
    import jax.numpy as jnp

    return jnp.where(finite, new, old)


def promote_readonly_scope_arrays(scope, compiled):
    """Gather the compiled block's read-only args, promoting host numpy
    values to device arrays ONCE (written back to the scope).

    Scope values can be host numpy — the analysis passes (e.g.
    ``fuse_conv_bn``) compute folded weights in numpy and store them:
    jit would re-transfer those on EVERY dispatch (measured in round 5:
    ResNet-50 inference 30x slower than its own training step,
    2.8s/batch ≈ the folded weights re-uploading per call).  rw values need no promotion: they are
    donated on call and the scope is refreshed from the jit's device
    outputs (promoting them here would leave donated buffers in the
    scope if the call raises).  Under SPMD, ``param_shardings`` places
    the promoted array with its compiled in_sharding directly."""
    import jax

    ro = {}
    for n in compiled.ro_names:
        v = scope.get(n)
        if isinstance(v, np.ndarray):
            if compiled.param_shardings is not None:
                v = jax.device_put(v, compiled.param_shardings[n])
            else:
                v = jax.device_put(v)
            scope.set(n, v)
        ro[n] = v
    return ro


def _trace_partitioned(run_block):
    """Trace ``run_block`` as a step GSPMD partitions: the op lowerings
    route around the Mosaic kernels, which cannot be partitioned
    automatically (``ops.pallas.gspmd_partitioned``)."""
    from .ops.pallas import gspmd_partitioned

    def traced(*args):
        with gspmd_partitioned():
            return run_block(*args)

    return traced


class _CompiledBlock:
    def __init__(self, program, block, feed_names, fetch_names, scope, mode,
                 mesh=None, accumulate_steps=1, trip_counts=None,
                 iters_per_run=1, shard_opt_state=False, nan_guard=False):
        import jax

        global _LAST_COMPILED_BLOCK
        _LAST_COMPILED_BLOCK = self

        self.feed_names = list(feed_names)
        self.fetch_names = list(fetch_names)
        self.nan_guard = bool(nan_guard)
        self.accumulate_steps = int(accumulate_steps or 1)
        self.iters_per_run = int(iters_per_run or 1)
        self.shard_opt_state = bool(shard_opt_state) and mesh is not None
        if self.accumulate_steps > 1 and self.iters_per_run > 1:
            raise ValueError(
                "num_iteration_per_run cannot combine with "
                "batch_merge_repeat: both wrap the step in a scan")
        self.trip_counts = dict(trip_counts or {})
        # what the grad op of each Mosaic kernel site took when the step
        # was traced (``LoweringContext.residual_sites``), and the open
        # ``compile`` phase that reports it after the first dispatch
        self.residual_sites = {}
        # the grid blocks of the flash kernels the step's last trace held
        # (``flash_attention.noting_blocks``), reported the same way
        self.flash_blocks = {}
        self.compile_phase = None
        ext_reads, written, persist_written = _analyze_block(
            block, feed_names, fetch_names
        )
        # every gradient the block produces joins the finite-guard check
        # (plus the inexact fetches — the loss — checked at step time).
        # A block producing NO gradients (startup, inference) has no
        # update to skip: the guard downgrades to off so those runs
        # neither pay the extra sync nor inflate the skip counters.
        self._guard_grad_names = (
            [n for n in dict.fromkeys(written) if "@GRAD" in n]
            if self.nan_guard else [])
        self.nan_guard = self.nan_guard and bool(self._guard_grad_names)
        # vars read from scope, split into mutated (donated) vs read-only
        self.rw_names = [n for n in ext_reads if n in persist_written]
        self.ro_names = [n for n in ext_reads if n not in persist_written]
        # persistables written but never read (e.g. startup init, fresh
        # accumulators) are also returned to the scope
        self.fresh_persist = [n for n in persist_written if n not in self.rw_names]
        self.block = block
        self.mode = mode

        missing = [n for n in ext_reads if not scope.has(n)]
        if missing:
            data_vars = []
            state_vars = []
            for n in missing:
                v = block._find_var_recursive(n)
                (data_vars if v is not None and v.is_data else
                 state_vars).append(n)
            msgs = []
            if data_vars:
                msgs.append(
                    "data variables %s were not fed — pass them in `feed`"
                    % data_vars
                )
            if state_vars:
                msgs.append(
                    "variables %s are not initialized in scope — run the "
                    "startup program first" % state_vars
                )
            raise RuntimeError(
                "; ".join(msgs)
                + " (reference: executor.cc enforce 'Tensor holds no memory')"
            )

        # host-IO ops of the TOP block run host-side around the jitted
        # call; in sub-blocks they must fail loudly, so the filter lives
        # here, not in _run_ops_into_env.  (Program mutation invalidates
        # this _CompiledBlock via the _version cache key, so snapshotting
        # the op list here is safe.)
        _top_ops = [op for op in block.ops
                    if op.type not in _HOST_SIDE_OPS]

        def step_once(feeds, rw, ro, key):
            """One whole train/infer step — shared by the plain path and
            the num_iteration_per_run scan so the two cannot drift."""
            env = {}
            env.update(ro)
            env.update(rw)
            env.update(feeds)
            ctx = op_registry.LoweringContext(base_key=key, mode=mode)
            ctx.trip_counts = self.trip_counts
            ctx.residual_sites = self.residual_sites
            gate = feeds.get(_FAULT_GATE_FEED)
            if gate is not None:
                from .resilience import faults as _rfaults

                ctx.fault_value_hook = _rfaults.get_injector() \
                    .make_value_hook(gate, loss_name=getattr(
                        program, "_guard_loss_name", None))
            with noting_blocks(collections.Counter()) as self.flash_blocks:
                _run_ops_into_env(block, env, ctx, ops=_top_ops)
            fetches = [env[n] for n in self.fetch_names]
            new_rw = {n: env[n] for n in self.rw_names}
            fresh = {n: env[n] for n in self.fresh_persist if n in env}
            if self.nan_guard:
                finite = _all_finite(
                    [env.get(n) for n in self._guard_grad_names]
                    + fetches)
                new_rw = {n: _guard_select(finite, v, rw[n])
                          for n, v in new_rw.items()}
                # the flag rides the fetch list back to the host, where
                # guard.record_step keeps the skip counter
                fetches = fetches + [finite]
            return fetches, new_rw, fresh

        if self.accumulate_steps > 1:
            run_block = _AccumRunner(self, block, mode)
        elif self.iters_per_run > 1:
            # ExecutionStrategy.num_iteration_per_run
            # (execution_strategy.h:42): K whole train steps inside ONE
            # dispatch, as a lax.scan carrying the mutable state.  One
            # launch + one host roundtrip amortizes over K steps — on
            # TPU this is how real training loops run; dropout draws a
            # fresh key per iteration, in-graph counters advance per
            # iteration, and fetches report the FINAL iteration (the
            # reference returns the last Run's fetch too).  Each
            # iteration consumes the same fed batch; pair with the
            # dataset runtime for distinct per-iteration batches.
            # Fetch/fresh values ride the CARRY (zero-init from an
            # abstract eval), so memory stays O(1) in K — no K-stacked
            # ys buffers.
            iters = self.iters_per_run

            def run_block(feeds, rw, ro, key):
                import jax.numpy as jnp

                f_s, _, fr_s = jax.eval_shape(step_once, feeds, rw, ro,
                                              key)
                f0 = [jnp.zeros(s.shape, s.dtype) for s in f_s]
                if self.nan_guard:
                    # the guard flag (last fetch) AND-folds across the
                    # scanned iterations — one non-finite iteration
                    # anywhere in the dispatch must surface, not just
                    # the final iteration's verdict
                    f0[-1] = jnp.ones(f_s[-1].shape, f_s[-1].dtype)
                fr0 = {n: jnp.zeros(s.shape, s.dtype)
                       for n, s in fr_s.items()}

                def body(carry, idx):
                    rw_c, f_prev = carry[0], carry[1]
                    f, nrw, fr = step_once(
                        feeds, rw_c, ro, jax.random.fold_in(key, idx))
                    if self.nan_guard:
                        f = f[:-1] + [jnp.logical_and(f[-1],
                                                      f_prev[-1])]
                    return (nrw, f, fr), None

                (rw_f, fetches, fresh), _ = jax.lax.scan(
                    body, (rw, f0, fr0),
                    jnp.arange(iters, dtype=jnp.int32))
                return fetches, rw_f, fresh
        else:
            run_block = step_once

        if mesh is None:
            self.param_shardings = None
            self.jitted = jax.jit(run_block, donate_argnums=(1,))
        else:
            # SPMD: batch dim of every feed sharded over the mesh's data
            # axis; params replicated EXCEPT is_distributed embedding
            # tables (+ their table-shaped optimizer accumulators), which
            # are row-sharded over the same axis — the PS/distributed-
            # lookup-table replacement (GSPMD partitions the lookup and
            # its scatter grad with the id exchange over ICI)
            from jax.sharding import NamedSharding, PartitionSpec as P

            if mesh.size > 1:
                run_block = _trace_partitioned(run_block)
            data_axis = mesh.axis_names[0]
            batch = NamedSharding(mesh, P(data_axis))
            repl = NamedSharding(mesh, P())

            def param_sharding(n):
                v = block._find_var_recursive(n)
                if v is None:
                    return repl
                spec = getattr(v, "shard_spec", None)
                if spec is not None and v.shape:
                    # TP annotation (ParamAttr.shard_spec): validate axes +
                    # divisibility, else fall back replicated with a warning
                    import warnings

                    ok = len(spec) <= len(v.shape)
                    if ok:
                        for i, ax in enumerate(spec):
                            if ax is None:
                                continue
                            if (ax not in mesh.axis_names
                                    or v.shape[i] % mesh.shape[ax]):
                                ok = False
                                break
                    if ok:
                        return NamedSharding(mesh, P(*spec))
                    warnings.warn(
                        "shard_spec %r of %r does not fit mesh %s / shape "
                        "%s; replicating" % (spec, n, dict(mesh.shape),
                                             v.shape))
                # row-shard over the data axis: distributed embedding
                # tables always; optimizer accumulators under ZeRO-1
                # (BuildStrategy.shard_optimizer_state — per-chip
                # optimizer memory drops by dp_degree; GSPMD shards the
                # elementwise update and all-gathers only the param)
                if v.shape and (
                        getattr(v, "_is_distributed", False)
                        or (self.shard_opt_state
                            and getattr(v, "_is_optimizer_state", False)
                            and v.shape[0] % mesh.shape[data_axis] == 0)):
                    return NamedSharding(
                        mesh, P(data_axis, *([None] * (len(v.shape) - 1)))
                    )
                return repl

            feed_sh = {n: batch for n in self.feed_names}
            rw_sh = {n: param_sharding(n) for n in self.rw_names}
            ro_sh = {n: param_sharding(n) for n in self.ro_names}
            self.param_shardings = dict(ro_sh)
            # pin state OUTPUT shardings to the input classification:
            # under shard_opt_state GSPMD would otherwise follow the
            # sharded moments and emit the updated PARAM sharded too
            # (ZeRO-3 creep) — the next dispatch's replicated in_sharding
            # then rejects the arg.  Fetches/fresh stay None (XLA picks).
            self.jitted = jax.jit(
                run_block,
                donate_argnums=(1,),
                in_shardings=(feed_sh, rw_sh, ro_sh, repl),
                out_shardings=(None, rw_sh, None),
            )


def _accum_partition(block):
    """Split the block at the first optimize-role op for microbatch
    gradient accumulation (reference ``ir/multi_batch_merge_pass.cc``:
    the forward+backward subgraph is repeated per microbatch, optimizer
    ops run once on the merged gradients)."""
    ops = [op for op in block.ops if op.type not in _HOST_SIDE_OPS]
    split = next(
        (i for i, op in enumerate(ops)
         if op.attrs.get("op_role") == "optimize"),
        len(ops),
    )
    head, tail = ops[:split], ops[split:]
    head_written = set()
    for op in head:
        head_written.update(op.output_arg_names)
    tail_reads = []
    for op in tail:
        for n in op.input_arg_names:
            if (n and n != EMPTY_VAR_NAME and n in head_written
                    and n not in tail_reads):
                tail_reads.append(n)
    grad_reads = [n for n in tail_reads if "@GRAD" in n]
    other_reads = [n for n in tail_reads if "@GRAD" not in n]
    return head, tail, head_written, grad_reads, other_reads


class _AccumRunner:
    """run_block variant that scans the forward+backward ops over k
    microbatches (feeds reshaped [k, B/k, ...]), averages the gradients,
    then runs the optimizer ops once — lax.scan keeps ONE compiled copy of
    the model in HBM regardless of k (vs the reference pass's k-times
    graph replication).

    Caveat (documented): in-graph counters written by pre-optimizer ops
    (e.g. lr-scheduler step counters) advance once per MICRObatch."""

    def __init__(self, cb, block, mode):
        self.cb = cb
        self.block = block
        self.mode = mode
        (self.head, self.tail, self.head_written, self.grad_reads,
         self.other_reads) = _accum_partition(block)
        # head-written values the caller needs: fetches + persistables
        carry_out = list(self.other_reads)
        for n in cb.fetch_names + cb.rw_names + cb.fresh_persist:
            if n in self.head_written and n not in carry_out \
                    and n not in self.grad_reads:
                carry_out.append(n)
        self.carry_out = carry_out

    def __call__(self, feeds, rw, ro, key):
        import jax
        import jax.numpy as jnp

        cb, k = self.cb, self.cb.accumulate_steps
        base_env = {}
        base_env.update(ro)
        base_env.update(rw)
        # the fault gate is per-step metadata, not batch data: keep it
        # out of the microbatch reshape and hand it to the hook directly
        gate = feeds.get(_FAULT_GATE_FEED)
        fault_hook = None
        if gate is not None:
            from .resilience import faults as _rfaults

            fault_hook = _rfaults.get_injector().make_value_hook(
                gate, loss_name=getattr(self.block.program,
                                        "_guard_loss_name", None))
        micro = {}
        for n, v in feeds.items():
            if n == _FAULT_GATE_FEED:
                continue
            b = v.shape[0]
            if b % k:
                raise ValueError(
                    "batch dim %d of feed %r is not divisible by "
                    "accumulate_steps=%d" % (b, n, k))
            micro[n] = v.reshape((k, b // k) + v.shape[1:])

        def head_fn(mf, idx):
            e = dict(base_env)
            e.update(mf)
            ctx = op_registry.LoweringContext(
                base_key=jax.random.fold_in(key, idx), mode=self.mode)
            ctx.fault_value_hook = fault_hook
            ctx.residual_sites = cb.residual_sites
            with noting_blocks(collections.Counter()) as cb.flash_blocks:
                _run_ops_into_env(self.block, e, ctx, ops=self.head)
            return (
                {n: e[n] for n in self.grad_reads},
                {n: e[n] for n in self.carry_out if n in e},
            )

        shapes = jax.eval_shape(
            head_fn, {n: v[0] for n, v in micro.items()}, 0)
        acc0 = {n: jnp.zeros(s.shape, s.dtype)
                for n, s in shapes[0].items()}

        def body(carry, mf):
            idx, acc = carry
            grads, outs = head_fn(mf, idx)
            acc = {n: acc[n] + grads[n].astype(acc[n].dtype) for n in acc}
            return (idx + 1, acc), outs

        (_, acc), stacked = jax.lax.scan(
            body, (jnp.asarray(0, jnp.int32), acc0), micro)

        micro_bs = next(iter(micro.values())).shape[1] if micro else None
        env = dict(base_env)
        for n in self.carry_out:
            if n not in stacked:
                continue
            v = stacked[n]
            is_state = n in cb.rw_names or n in cb.fresh_persist
            if n in cb.fetch_names and not is_state:
                # per-sample outputs ([k, B/k, ...]) reassemble to the full
                # batch; per-step scalars (losses/metrics) report the
                # microbatch average (the full-batch mean for mean losses)
                if (micro_bs is not None and v.ndim >= 2
                        and v.shape[1] == micro_bs):
                    env[n] = v.reshape((k * micro_bs,) + v.shape[2:])
                elif jnp.issubdtype(v.dtype, jnp.inexact):
                    env[n] = jnp.mean(v, axis=0)
                else:
                    env[n] = v[-1]
            else:
                # state (persistables, counters): last microbatch's value
                env[n] = v[-1] if v.shape[0] == k else v
        for n in self.grad_reads:
            env[n] = acc[n] / jnp.asarray(k, acc[n].dtype)
        ctx = op_registry.LoweringContext(base_key=key, mode=self.mode)
        ctx.fault_value_hook = fault_hook
        _run_ops_into_env(self.block, env, ctx, ops=self.tail)
        fetches = [env[n] for n in cb.fetch_names]
        new_rw = {n: env[n] for n in cb.rw_names}
        fresh = {n: env[n] for n in cb.fresh_persist if n in env}
        if cb.nan_guard:
            finite = _all_finite(
                [env.get(n) for n in self.grad_reads] + fetches)
            new_rw = {n: _guard_select(finite, v, rw[n])
                      for n, v in new_rw.items()}
            fetches = fetches + [finite]
        return fetches, new_rw, fresh


def _host_table_prefetch(program, feed, feed_vals):
    """Host-table step-prefetch shared by the Executor and the SPMD
    runner (parameter_prefetch.cc role): gather each batch's rows into
    the dense slab feed.  Returns (host_active, grad_fetch_names)."""
    import jax
    import jax.numpy as jnp

    host_specs = getattr(program, "_host_tables", None) or []
    host_active = []
    if host_specs and jax.process_count() > 1:
        raise RuntimeError(
            "host_embedding under a multi-process cluster would let each "
            "process's table replica drift (each only sees its local "
            "grads); use embedding(is_distributed=True) row-sharded "
            "tables for multi-host, or a single-process mesh")
    for spec in host_specs:
        from . import host_table as _host_table

        tab = _host_table.get_table(spec["table"])
        if spec["ids"] not in feed:
            raise RuntimeError(
                "host_embedding ids var %r must be fed directly — "
                "the host-side prefetch reads its value before the "
                "device step" % spec["ids"])
        ids_np = np.asarray(feed[spec["ids"]])
        feed_vals[spec["slab"]] = jnp.asarray(tab.lookup(ids_np))
        gname = spec["slab"] + "@GRAD"
        has_grad = (program.global_block()
                    ._find_var_recursive(gname) is not None)
        host_active.append((tab, ids_np, gname if has_grad else None))
    return host_active, [g for _, _, g in host_active if g]


def _host_table_push(host_active, fetches, n_user):
    """Async-push the fetched slab grads; returns the user fetches."""
    gi = n_user
    for tab, ids_np, g in host_active:
        if g is not None:
            tab.update_async(ids_np, np.asarray(fetches[gi]))
            gi += 1
    return fetches[:n_user]


def _apply_step_results(compiled, scope, fetches, new_rw, fresh,
                        fetch_names, host_active, host_grad_fetches,
                        step):
    """Post-dispatch protocol shared by Executor.run and SPMDRunner.run.

    Async contract: device outputs are written back to the scope AS
    DEVICE ARRAYS — no host copy here, so the step stays in flight and
    the caller's fetch handles decide when (and whether) to sync.  The
    one exception is the opt-in NaN step-guard, whose scalar finite flag
    must reach the host every step (skip bookkeeping may raise on a
    diverged run) — guarded training pays one scalar sync per step by
    design.

    Order matters: the donated rw state must reach the scope FIRST (its
    old buffers are gone; the guard already reverted a non-finite step
    in-graph), then the guard flag is stripped and recorded — which may
    raise on a diverged run, leaving the scope consistent — and only a
    finite step applies write-only persistables and the host-table grad
    push: a skipped step must leave host tables and fresh persistables
    exactly as untouched as the params."""
    from .resilience import guard as _rguard

    for n, v in new_rw.items():
        scope.set(n, v)
    step_finite = True
    if compiled.nan_guard:
        # last fetch is the in-graph all-finite flag; a cold flag means
        # this step's update was skipped in-graph
        finite_flag = fetches[-1]
        fetches = fetches[:-1]
        step_finite = _rguard.record_step(bool(np.asarray(finite_flag)),
                                          step=step)
    if step_finite:
        for n, v in fresh.items():
            scope.set(n, v)
    if host_grad_fetches:
        n_user = len(fetch_names) - len(host_grad_fetches)
        if step_finite:
            fetches = _host_table_push(host_active, fetches, n_user)
        else:
            fetches = fetches[:n_user]
    return fetches


def _run_ops_into_env(block, env, ctx, ops=None):
    """Lower ops of `block` (all, or the given subset) into `env` (the SSA
    value map).

    Every op's lowering is wrapped in a ``jax.named_scope`` carrying the
    Program op type + block position (``pd<idx>_<type>``).  The scope
    rides the jaxpr into HLO op metadata, so device profiles (XPlane)
    can be attributed back to Program ops — the whole-block jit makes
    host-side per-op timing impossible, and this is the device-side
    equivalent of the reference's per-op profiler tables
    (platform/profiler.h:166).  Trace-time only: zero runtime cost.
    An op built under ``framework.device_tag`` is named after its tag;
    a lowering names its own parts through ``ctx.part_scope``.

    **One forward per Mosaic kernel site.**  A generic grad op re-derives
    its forward under ``jax.vjp``; XLA merges that with the forward op
    where both are XLA ops, never where they are a Mosaic custom call.
    So where a forward op's site routes to such a kernel
    (``OpDef.kernel_residuals``) and its grad twin is in THIS call's op
    list, the forward is lowered once, through ``jax.vjp``, and kept
    (``kept``, local to this call: no tracer crosses into another trace,
    and a list with no backward lowers exactly as before); the grad op,
    if it is fed the very values the forward saw, takes the kept
    residuals.  Anything else is the generic arm.  What each kernel site
    took is noted in ``ctx.residual_sites`` (if the caller set one).

    Inside a ``recompute_block`` region the grad is one ``jax.vjp`` over
    the region run again, so a forward kernel there would run in both.
    Where the region's grad op is in THIS call's op list, the two share
    a ``registry.RegionKept`` (in ``kept`` too, under the region's op
    id): a kernel site's lowering puts into it what its forward kernel
    computed and its backward kernels read (flash attention: ``o, m,
    l``; never an activation, which is what the region drops), and the
    re-run takes that in place of a second forward kernel
    (``kept_across_region``).  A region whose two ops are lowered by
    different calls keeps nothing and reads ``recomputed``."""
    import jax

    from .ops import control_flow as cf_ops

    fault_hook = getattr(ctx, "fault_value_hook", None)
    ops = block.ops if ops is None else ops
    twin_ids = {op.attrs["__fwd_op_id__"] for op in ops
                if "__fwd_op_id__" in op.attrs}
    kept = {}   # forward op id -> registry.KeptForward | RegionKept
    for i, op in enumerate(ops):
        if op.type in ("feed", "fetch"):
            continue
        op_id = op.attrs.get("__fwd_op_id__", op.attrs.get("__op_id__", 0))
        if op.type in cf_ops.SUB_BLOCK_OPS:
            # control-flow ops need names + the sub-block, not just values
            if op.type == "recompute_block" and op_id in twin_ids:
                kept[op_id] = op_registry.RegionKept()
            with jax.named_scope("pd%d_%s" % (i, op.type)):
                cf_ops.run_sub_block_op(op, block, env, ctx,
                                        _run_ops_into_env, kept.get(op_id))
            continue
        opdef = op_registry.get_op_def(op.type)
        ins = {}
        for slot, names in op.inputs.items():
            vals = []
            for n in names:
                if not n or n == EMPTY_VAR_NAME:
                    vals.append(None)
                else:
                    vals.append(env.get(n))
            ins[slot] = vals
        ctx.op_scope = "pd%d_%s" % (i, op.attrs.get("device_tag", op.type))
        with jax.named_scope(ctx.op_scope):
            if (op.attrs.get("__op_id__") in twin_ids
                    and op_registry.routes_to_kernel(opdef, ctx, ins,
                                                     op.attrs)):
                outs, kept[op_id] = op_registry.call_op_keeping_vjp(
                    opdef, ctx, ins, op.attrs, op_id=op_id)
            elif opdef.fwd_def is not None \
                    and opdef.fwd_def.kernel_residuals is not None:
                outs = _lower_kernel_site_grad(opdef, ctx, ins, op.attrs,
                                               op_id, kept.get(op_id))
            else:
                outs = op_registry.call_op(opdef, ctx, ins, op.attrs,
                                           op_id=op_id)
        for slot, names in op.outputs.items():
            vals = outs.get(slot)
            if vals is None:
                continue
            for n, v in zip(names, vals):
                if n and n != EMPTY_VAR_NAME and v is not None:
                    if fault_hook is not None:
                        v = fault_hook(n, v)
                    env[n] = v
    return env


def _lower_kernel_site_grad(opdef, ctx, ins, attrs, fwd_id, kept):
    """The generic grad of an op that can hold a Mosaic kernel: over the
    forward twin's kept residuals where there are any and this op is fed
    the very values the forward saw, else re-deriving the forward.  Notes
    in ``ctx.residual_sites`` which of the two a kernel site took."""
    if kept is not None and not kept.saw(ins):
        kept = None
    outs = op_registry.call_op(opdef, ctx, ins, attrs, op_id=fwd_id,
                               kept=kept)
    if kept is not None:
        ctx.note_residual_site(opdef.fwd_def.type, "reused")
    elif op_registry.routes_to_kernel(opdef, ctx, ins, attrs):
        ctx.note_residual_site(opdef.fwd_def.type, "recomputed")
    return outs


def _check_feed_shapes(program, feed_vals):
    """Validate fed arrays against declared ``layers.data`` shapes
    (reference executor's check_feed_shape_type on need_check_feed vars).

    Only rank-equal feeds with a static declared dim that disagrees are
    rejected — -1 dims (batch, ragged) accept anything, and rank
    differences are left to the lowering (some callers feed unbatched
    scalars).  A builder-attached ``var.feed_hint`` is appended so model
    contracts (e.g. bert's masked-gather head) produce targeted errors
    instead of a jit shape failure deep in the stack."""
    block = program.global_block()
    for name, value in feed_vals.items():
        var = block.vars.get(name)
        if var is None or not getattr(var, "need_check_feed", False):
            continue
        declared = var.shape
        got = tuple(getattr(value, "shape", ()))
        if declared is None or len(declared) != len(got):
            continue
        for d_decl, d_got in zip(declared, got):
            if d_decl >= 0 and d_decl != d_got:
                hint = getattr(var, "feed_hint", None)
                raise ValueError(
                    "feed %r has shape %s but the data layer declares %s "
                    "(dim %d != %d)%s"
                    % (name, got, tuple(declared), d_got, d_decl,
                       ("\n" + hint) if hint else ""))


def _stage_feeds(runner, feed, cache, batch_sharding=None,
                 check_against=None):
    """The ``feed_stage`` phase of both runners: every fed value onto
    the device.  A chained :class:`FetchHandle` gives its device value; a
    host array goes through the placement cache (the SAME array re-fed
    step after step, a constant mask or a benchmark batch, is copied
    once; reference ``_feed_data`` → ``set_feed_variable``); device
    arrays, staged by ``DeviceFeedPipeline`` say, pass through free.
    ``batch_sharding`` (a multi-process cluster, reference nccl2 mode):
    each process feeds its LOCAL batch shard and the global batch-sharded
    array is assembled over the cross-process mesh (the reference's
    feed_and_split_tensor_into_local_scopes, inverted).  The staged
    values are held to the data layers ``check_against`` declares."""
    import jax
    import jax.numpy as jnp

    feed_vals = {}
    with _tr.phase(runner + ".feed_stage", bytes=0, hits=0, misses=0):
        for name, value in feed.items():
            if batch_sharding is not None:
                value = jax.make_array_from_process_local_data(
                    batch_sharding, np.asarray(value))
            elif isinstance(value, FetchHandle):
                value = value.device_value
            if isinstance(value, np.ndarray):
                value = _pipeline._stage(value, name=name, cache=cache)
            elif isinstance(value, (list, tuple, int, float)):
                value = jnp.asarray(value)
            feed_vals[name] = value
        if check_against is not None:
            _check_feed_shapes(check_against, feed_vals)
    return feed_vals


def _feed_signature(feed_vals):
    return tuple((n, tuple(v.shape), str(v.dtype))
                 for n, v in sorted(feed_vals.items()))


def _compile_step(runner, build, program, feed_vals, fetch_names):
    """The ``compile`` phase of both runners: ``build()`` makes the
    :class:`_CompiledBlock` (trace, lower, jit)."""
    with _tr.phase(runner + ".compile") as ph:
        compiled = build()
    compiled.compile_phase = ph
    ph.set_attr("compile_ms", round(ph.dur_ms, 2))
    _obs.record_compile(ph.dur_ms, runner=runner)
    _obs.record_moe_layers(program, ph)
    _obs.record_attention_layers(program, ph)
    _register_compile_telemetry(compiled, program, feed_vals, fetch_names)
    return compiled


def _dispatch_step(runner, step_phase, compiled, program, scope, feed_vals,
                   executor, cur_step, fetch_names, host_active,
                   host_grad_fetches, return_numpy, has_host_io=False):
    """From the compiled block to the caller's fetches: the phases
    ``gather_state``, ``rng_key``, ``dispatch``, ``apply_results`` and
    ``finish_fetches`` of both runners, then the step's telemetry.

    ``dispatch`` is the jitted call alone: under jax async dispatch it
    returns once the step is ENQUEUED.  With ``return_numpy=False``
    nothing here waits for the device, so no step time is recorded: the
    handles carry the step and its dispatch stamp to the place that
    materialises them (``pipeline.host_values``, a ``host.sync``
    phase)."""
    import jax

    with _tr.phase(runner + ".gather_state", n_rw=len(compiled.rw_names),
                   n_ro=len(compiled.ro_names)):
        rw = {n: scope.get(n) for n in compiled.rw_names}
        ro = promote_readonly_scope_arrays(scope, compiled)
    with _tr.phase(runner + ".rng_key"):
        base_key = jax.random.fold_in(
            rng_key(program.random_seed or 0), executor._step)
    executor._step += 1
    # per-ring collective launches ride as attributes (cheap, and a
    # per-launch span would dwarf the thing it measures)
    with _tr.phase(runner + ".dispatch",
                   **_obs.collective_step_shape()) as dispatch:
        fetches, new_rw, fresh = compiled.jitted(feed_vals, rw, ro, base_key)
    if compiled.compile_phase is not None:
        # this block's first dispatch: jax.jit traced the step just now
        _obs.record_grad_residual_sites(
            compiled.residual_sites.values(), compiled.compile_phase)
        _obs.record_flash_blocks(compiled.flash_blocks,
                                 compiled.compile_phase)
        compiled.compile_phase = None
    with _tr.phase(runner + ".apply_results"):
        fetches = _apply_step_results(
            compiled, scope, fetches, new_rw, fresh, fetch_names,
            host_active, host_grad_fetches, cur_step)
        if has_host_io:
            from .ops.io_ops import run_host_io_block

            run_host_io_block(program.global_block(), scope, phase="save")
        # the scope holds the new state: the old (donated) arrays die
        # here, in the phase that replaced them, not in the step's self
        # time when this frame goes (a millisecond for 750 of them)
        del rw, ro, new_rw, fresh
    drift_key = getattr(compiled, "_drift_key", None)
    with _tr.phase(runner + ".finish_fetches",
                   handles=0 if return_numpy else len(fetches)):
        result = _finish_fetches(
            fetches, return_numpy, fetch_names=fetch_names,
            state_names=(tuple(compiled.rw_names)
                         + tuple(compiled.fresh_persist)),
            step_info=(runner, cur_step, dispatch.t1_ns, drift_key,
                       executor._last_done))
    _obs.record_step(
        runner, cur_step, dispatch.dur_ms,
        wall_ms=(_time.perf_counter_ns() - step_phase.t0_ns) / 1e6
        if return_numpy else None,
        drift_key=drift_key, last_done=executor._last_done)
    return result


class Executor:
    """Reference API: ``Executor(place).run(program, feed, fetch_list)``
    (``python/paddle/fluid/executor.py:565``)."""

    def __init__(self, place=None):
        self.place = place if place is not None else core.TPUPlace(0)
        self._cache = {}
        self._feed_cache = _pipeline.FeedCache()
        self._step = 0
        # [step, perf_counter_ns] of the newest step of this executor
        # seen complete (observability.runtime.record_step_done): step
        # numbers are per executor, so the intervals between them are too
        self._last_done = [None, 0]

    def close(self):
        self._cache.clear()
        self._feed_cache.clear()

    def run(
        self,
        program=None,
        feed=None,
        fetch_list=None,
        feed_var_name="feed",
        fetch_var_name="fetch",
        scope=None,
        return_numpy=True,
        use_program_cache=True,
        use_prune=False,
        verify=False,
        _fusion_config=None,
    ):
        from .compiler import CompiledProgram

        if program is None:
            program = default_main_program()
        if verify:
            # opt-in debug hook: catch malformed programs (dangling reads
            # after a bad pass, dtype drift, double writes aliasing the
            # donated param buffers) with structured diagnostics BEFORE
            # they become opaque trace-time errors
            from .static_analysis import assert_valid

            to_verify = (getattr(program, "_program", None)
                         if isinstance(program, CompiledProgram)
                         else program)
            if to_verify is not None:
                assert_valid(
                    to_verify,
                    targets=[v.name if isinstance(v, Variable) else str(v)
                             for v in (fetch_list or [])],
                    header="Executor.run(verify=True): program failed "
                           "verification:")
        if isinstance(program, CompiledProgram):
            # feed checking must also cover the DP/ZeRO/ipr paths — the
            # wrapped program carries the declared data shapes
            if isinstance(feed, dict) and feed \
                    and getattr(program, "_program", None) is not None:
                _check_feed_shapes(program._program, feed)
            return program._run(self, feed, fetch_list, scope, return_numpy)
        # ---- resilience hooks (all no-ops without a fault spec /
        # PADDLE_TPU_NAN_GUARD — see resilience/) ----
        from .resilience import faults as _rfaults

        inj = _rfaults.get_injector()
        # fires worker_kill / worker_hang process faults at their step
        cur_step = inj.on_step() if inj.active else self._step
        # the whole call is the step: its parts are the phases below and
        # in _dispatch_step, and what no phase covers is its self time
        with _tr.phase("executor.step", step=cur_step, head_sample=True,
                       runner="executor",
                       lazy=not return_numpy) as step_phase:
            return self._run_step(
                step_phase, program, feed or {}, fetch_list or [],
                global_scope() if scope is None else scope, return_numpy,
                use_program_cache, _fusion_config, inj, cur_step)

    def _run_step(self, step_phase, program, feed, fetch_list, scope,
                  return_numpy, use_program_cache, fusion_config, inj,
                  cur_step):
        import jax.numpy as jnp

        fetch_names = [
            v.name if isinstance(v, Variable) else str(v) for v in fetch_list
        ]

        # ---- cost-guided fusion pass pipeline (static_analysis/fusion):
        # resolve the fusion-rewritten twin of the program (a cached
        # clone — the user's program is never mutated, and PADDLE_TPU_
        # FUSION=0 reproduces the pre-fusion numerics bit-exactly).  The
        # fetch names ride into the resolution so a fetched intermediate
        # is never fused away; the jit cache below keys on the resolved
        # program's identity/version + the fusion signature.
        # ``_fusion_config`` (CompiledProgram._run) carries the caller's
        # BuildStrategy-derived config — without it a config whose
        # passes all no-op would fall back to the default config here,
        # silently re-enabling families the user disabled.
        from .static_analysis import fusion as _fusion

        with _tr.phase("executor.fusion_resolve"):
            program, _fusion_report = _fusion.resolve_fused_program(
                program, config=fusion_config, targets=fetch_names)

        from .resilience import guard as _rguard
        from .resilience import retry as _rretry

        nan_guard = _rguard.guard_enabled(program)

        # save/load ops are host IO, never jitted (reference save_op.cc).
        # Loads run now (their outputs feed the compute), saves after the
        # jitted step's scope writeback; a pure-IO program skips jit.
        from .ops.io_ops import HOST_IO_OP_TYPES, run_host_io_block

        has_host_io = any(op.type in HOST_IO_OP_TYPES
                          for op in program.global_block().ops)
        if has_host_io:
            run_host_io_block(program.global_block(), scope, phase="load")
            if all(op.type in HOST_IO_OP_TYPES + ("feed", "fetch")
                   for op in program.global_block().ops):
                run_host_io_block(program.global_block(), scope,
                                  phase="save")
                vals = [scope.get(n) for n in fetch_names]
                # every value here is a live scope array — detach lazy
                # handles so a later step's donation can't gut them
                return _finish_fetches(vals, return_numpy,
                                       fetch_names=fetch_names,
                                       state_names=fetch_names)

        feed_vals = _stage_feeds("executor", feed, self._feed_cache,
                                 check_against=program)

        # fault-injection gate vector: one fed scalar per value fault, so
        # the step-dependent corruption never recompiles the block.
        # Training dispatches only — gate_vector() consumes firing
        # budgets, and an eval/startup run at the eligible step must not
        # silently burn the fault
        if inj.active and inj.trace_faults \
                and _is_training_program(program):
            feed_vals[_FAULT_GATE_FEED] = jnp.asarray(
                inj.gate_vector(cur_step))

        # host-resident embedding tables (parameter_prefetch.cc role):
        # prefetch each batch's rows into a dense slab feed; the slab's
        # gradient is fetched from the step and pushed back to the host
        # table on a background thread (communicator.h async push)
        host_active, host_grad_fetches = _host_table_prefetch(
            program, feed, feed_vals)
        fetch_names = fetch_names + host_grad_fetches

        with _tr.phase("executor.lookup"):
            # two-pass unbounded-while gradients: probe concrete trip
            # counts first; they become static scan lengths, so they join
            # the cache key (a longer loop must recompile)
            trip_counts = None
            if _has_unbounded_while_grad(program):
                trip_counts = _probe_trip_counts(
                    program.global_block(), feed_vals, scope, fetch_names)
            key_tuple = (
                id(program),
                program._version,
                id(scope),
                _feed_signature(feed_vals),
                tuple(fetch_names),
                tuple(sorted((trip_counts or {}).items())),
                nan_guard,
                # fusion config is part of the compilation identity: the
                # same source program under a different fusion config is
                # a different (cloned) program object, and the signature
                # makes the separation explicit/debuggable
                getattr(program, "_fusion_sig", None),
            )
            compiled = (self._cache.get(key_tuple) if use_program_cache
                        else None)
            _obs.record_jit_cache(compiled is not None)
        if compiled is None:
            def _compile():
                # injectable site (compile_fail) — and transient
                # backend/OS failures back off and retry instead of
                # killing an otherwise healthy run
                if inj.active:
                    inj.maybe_fire("compile", step=cur_step)
                return _CompiledBlock(
                    program,
                    program.global_block(),
                    list(feed_vals),
                    fetch_names,
                    scope,
                    "train",
                    trip_counts=trip_counts,
                    nan_guard=nan_guard,
                )

            compiled = _compile_step(
                "executor",
                lambda: _rretry.retry_call(_compile,
                                           site="executor.compile"),
                program, feed_vals, fetch_names)
            if use_program_cache:
                self._cache[key_tuple] = compiled

        return _dispatch_step(
            "executor", step_phase, compiled, program, scope, feed_vals,
            self, cur_step, fetch_names, host_active, host_grad_fetches,
            return_numpy, has_host_io=has_host_io)

    # ------ dataset entry points (reference executor.py:909) — see
    # paddle_tpu/trainer.py once the dataset path lands ------
    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        from .dataset_runtime import run_from_dataset

        return run_from_dataset(self, program, dataset, scope, fetch_list,
                                fetch_info, print_period, train=True)

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        from .dataset_runtime import run_from_dataset

        return run_from_dataset(self, program, dataset, scope, fetch_list,
                                fetch_info, print_period, train=False)
