"""SPMD data-parallel execution (replaces the reference's ParallelExecutor
stack: ``parallel_executor.cc:302``, ``multi_devices_graph_pass.cc``,
``details/*_op_handle*``, NCCL contexts ``nccl_helper.h``).

TPU-native model: ONE program, jitted once over a ``jax.sharding.Mesh`` with
the batch dim of every feed sharded over the ``data`` axis and params
replicated.  Because the program's loss reduction is over the *global* batch,
GSPMD emits the gradient all-reduce over ICI automatically — there is no
graph cloning, no per-gradient all-reduce insertion, no ring configuration.
The reference's BuildStrategy reduce/fuse/hierarchical knobs are subsumed by
the XLA partitioner.
"""

import numpy as np

from .. import core
from ..executor import (_CompiledBlock, _compile_step, _dispatch_step,
                        _feed_signature, _host_table_prefetch,
                        _stage_feeds, global_scope)
from ..observability import runtime as _obs
from ..observability import tracing as _tr
from ..framework import Variable, default_main_program

__all__ = ["ParallelExecutor", "SPMDRunner"]


def _make_mesh(places=None, num_devices=None, tp_degree=1):
    import jax
    from jax.sharding import Mesh

    devs = jax.devices()
    if places:
        devs = devs[: len(places)]
    elif num_devices:
        devs = devs[:num_devices]
    tp = max(1, int(tp_degree or 1))
    if tp > 1:
        if len(devs) % tp:
            raise ValueError(
                "tensor_parallel_degree=%d does not divide the %d-device "
                "mesh" % (tp, len(devs)))
        return Mesh(
            np.array(devs).reshape(len(devs) // tp, tp), ("data", "model"))
    return Mesh(np.array(devs), ("data",))


class SPMDRunner:
    """jit-with-shardings runner behind CompiledProgram.with_data_parallel."""

    def __init__(self, program, build_strategy=None, places=None,
                 data_parallel=True, exec_strategy=None):
        self.program = program
        self.build_strategy = build_strategy
        tp = int(getattr(build_strategy, "tensor_parallel_degree", 1) or 1)
        self.mesh = (_make_mesh(places, tp_degree=tp)
                     if data_parallel else None)
        self.accumulate_steps = int(
            getattr(build_strategy, "batch_merge_repeat", 1) or 1)
        self.iters_per_run = int(
            getattr(exec_strategy, "num_iteration_per_run", 1) or 1)
        # EITHER source enables ZeRO-1: the BuildStrategy flag, or the
        # program-level stamp the auto-parallelism planner's in-place
        # apply (planner.apply_plan) leaves — a default-constructed
        # BuildStrategy is indistinguishable from an explicit False, so
        # to disable a stamped program's sharding, clear the stamp
        # (program._shard_optimizer_state = False), not the flag
        self.shard_opt_state = bool(
            getattr(build_strategy, "shard_optimizer_state", False)
            or getattr(program, "_shard_optimizer_state", False))
        self._last_fusion_report = None
        self._cache = {}
        from ..pipeline import FeedCache

        self._feed_cache = FeedCache()

    def run(self, executor, feed, fetch_list, scope, return_numpy):
        # resilience hooks (see resilience/): process faults fire here
        # too, and the finite step-guard covers the DP/ZeRO paths.
        # (Value-fault gates stay single-process-executor-only — a fed
        # scalar cannot take the batch sharding this path pins on feeds.)
        from ..resilience import faults as _rfaults

        inj = _rfaults.get_injector()
        cur_step = inj.on_step() if inj.active else executor._step
        # the same phases as Executor.run, under this runner's name
        with _tr.phase("spmd.step", step=cur_step, head_sample=True,
                       runner="spmd", lazy=not return_numpy) as step_phase:
            return self._run_step(
                step_phase, executor, feed or {}, fetch_list or [],
                global_scope() if scope is None else scope, return_numpy,
                cur_step)

    def _run_step(self, step_phase, executor, feed, fetch_list, scope,
                  return_numpy, cur_step):
        import jax

        fetch_names = [
            v.name if isinstance(v, Variable) else str(v) for v in fetch_list
        ]

        # fusion pass pipeline, honoring the BuildStrategy.fuse_* flags
        # (cached clone; the wrapped program itself is never mutated)
        from ..static_analysis import fusion as _fusion

        with _tr.phase("spmd.fusion_resolve"):
            program, self._last_fusion_report = \
                _fusion.resolve_fused_program(
                    self.program,
                    config=_fusion.FusionConfig.from_build_strategy(
                        self.build_strategy),
                    targets=fetch_names)

        from ..resilience import guard as _rguard

        nan_guard = _rguard.guard_enabled(program)
        batch = None
        if jax.process_count() > 1 and self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            batch = NamedSharding(self.mesh, P(self.mesh.axis_names[0]))
        # same placement cache as Executor.run (the partitioner re-shards
        # the staged array on later dispatches)
        feed_vals = _stage_feeds("spmd", feed, self._feed_cache,
                                 batch_sharding=batch)
        # host-resident tables under DP: prefetch the GLOBAL batch's
        # slab (GSPMD shards it over the data axis like any feed)
        if (getattr(program, "_host_tables", None)
                and self.accumulate_steps > 1):
            raise RuntimeError(
                "host_embedding with batch_merge_repeat>1 is not "
                "supported: the accumulation scan reassembles slab "
                "grads per-microbatch WITHOUT the 1/k averaging applied "
                "to param grads, so the host push would be k-times too "
                "large — run host-table programs with "
                "batch_merge_repeat=1")
        if (getattr(program, "_host_tables", None)
                and self.iters_per_run > 1):
            raise RuntimeError(
                "host_embedding with num_iteration_per_run>1 is not "
                "supported: the slab is prefetched once per DISPATCH, so "
                "all K scanned iterations would reuse a stale lookup and "
                "only the final iteration's slab gradient reaches the "
                "host push — run host-table programs with "
                "num_iteration_per_run=1")
        host_active, host_grad_fetches = _host_table_prefetch(
            program, feed, feed_vals)
        fetch_names = fetch_names + host_grad_fetches
        with _tr.phase("spmd.lookup"):
            key_tuple = (id(program), program._version, id(scope),
                         _feed_signature(feed_vals), tuple(fetch_names),
                         nan_guard, getattr(program, "_fusion_sig", None))
            compiled = self._cache.get(key_tuple)
            _obs.record_jit_cache(compiled is not None, runner="spmd")
        if compiled is None:
            compiled = self._cache[key_tuple] = _compile_step(
                "spmd",
                lambda: _CompiledBlock(
                    program,
                    program.global_block(),
                    list(feed_vals),
                    fetch_names,
                    scope,
                    "train",
                    mesh=self.mesh,
                    accumulate_steps=self.accumulate_steps,
                    iters_per_run=self.iters_per_run,
                    shard_opt_state=self.shard_opt_state,
                    nan_guard=nan_guard,
                ),
                program, feed_vals, fetch_names)

        return _dispatch_step(
            "spmd", step_phase, compiled, program, scope, feed_vals,
            executor, cur_step, fetch_names, host_active,
            host_grad_fetches, return_numpy)


class ParallelExecutor:
    """Reference-API shim (``python/paddle/fluid/parallel_executor.py``) over
    the SPMD runner."""

    def __init__(self, use_cuda=False, loss_name=None, main_program=None,
                 share_vars_from=None, exec_strategy=None,
                 build_strategy=None, num_trainers=1, trainer_id=0,
                 scope=None):
        self._program = main_program or default_main_program()
        self._scope = scope or global_scope()
        self._runner = SPMDRunner(self._program, build_strategy,
                                  exec_strategy=exec_strategy)
        from .executor import Executor

        self._exe = Executor(core.TPUPlace(0))

    @property
    def device_count(self):
        return int(np.prod(self._runner.mesh.devices.shape))

    def run(self, fetch_list, feed=None, feed_dict=None, return_numpy=True):
        feed = feed if feed is not None else feed_dict
        return self._runner.run(
            self._exe, feed, fetch_list, self._scope, return_numpy
        )

    def drop_local_exe_scopes(self):
        """reference ParallelExecutor.drop_local_exe_scopes: frees the
        per-place scope buffers; the SPMD runner's only cached state is
        its jit cache, which this drops."""
        self._runner._cache.clear()


from .ring_attention import (ring_attention, ring_attention_local,  # noqa: E402,F401
                             ring_rotate)

__all__ += ["ring_attention", "ring_attention_local", "ring_rotate"]

from .pipeline import gpipe, gpipe_stage_params, transpile_pipeline  # noqa: E402,F401

__all__ += ["gpipe", "gpipe_stage_params", "transpile_pipeline"]

from .ulysses import (ulysses_attention, ulysses_attention_local,  # noqa: E402,F401
                      ulysses_to_heads, ulysses_to_seq)

__all__ += ["ulysses_attention", "ulysses_attention_local",
            "ulysses_to_heads", "ulysses_to_seq"]

from .dgc import dgc_exchange, dgc_momentum_step  # noqa: E402,F401

__all__ += ["dgc_exchange", "dgc_momentum_step"]

from .moe import (moe_ffn, moe_ffn_local, init_moe_params,  # noqa: E402,F401
                  moe_dispatch, moe_combine)

__all__ += ["moe_ffn", "moe_ffn_local", "init_moe_params",
            "moe_dispatch", "moe_combine"]

from .planner import (ClusterSpec, PlanCandidate, PlanResult,  # noqa: E402,F401
                      auto_transpile)

__all__ += ["ClusterSpec", "PlanCandidate", "PlanResult",
            "auto_transpile"]
