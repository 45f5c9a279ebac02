"""CompiledProgram + build/exec strategies (reference:
``python/paddle/fluid/compiler.py`` + ``details/build_strategy.h:36``).

The reference's ``with_data_parallel`` constructs a C++ ParallelExecutor
that clones the graph per GPU and inserts NCCL all-reduce op-handles
(``multi_devices_graph_pass.cc:454``).  TPU-native, the same call records a
``jax.sharding.Mesh`` over the data axis and the executor jits the SAME
program with batch-sharded inputs and replicated params — GSPMD emits the
grad all-reduce over ICI.  The BuildStrategy knobs that survive are the ones
XLA doesn't subsume: donation, remat, and the ``fuse_*`` family — which
since the fusion-pipeline PR drive REAL cost-guided Program-IR rewrites
(``static_analysis/fusion.py``: Pallas attention/LN kernels, fused
bias+act, one-op softmax+xent, bucketed gradient allreduce).
``fuse_all_optimizer_ops`` (XLA fuses each parameter's update itself) and
reduce-strategy / hierarchical-allreduce (GSPMD always emits fused ring
allreduce) remain accepted-for-parity no-ops.
"""

__all__ = ["CompiledProgram", "BuildStrategy", "ExecutionStrategy"]


class BuildStrategy:
    class ReduceStrategy:
        AllReduce = 0
        Reduce = 1

    class GradientScaleStrategy:
        CoeffNumDevice = 0
        One = 1
        Customized = 2

    def __init__(self):
        self.reduce_strategy = BuildStrategy.ReduceStrategy.AllReduce
        self.gradient_scale_strategy = (
            BuildStrategy.GradientScaleStrategy.CoeffNumDevice
        )
        self.memory_optimize = False
        self.enable_inplace = True  # buffer donation
        # the fuse_* knobs drive the REAL cost-guided fusion pass
        # pipeline (static_analysis/fusion.py), the TPU realization of
        # the reference's fuse_all_reduce_op_pass /
        # fuse_elewise_add_act_pass:
        #   fuse_all_reduce_ops      -> bucketed gradient allreduce
        #                               (PADDLE_TPU_ALLREDUCE_BUCKET_MB)
        #   fuse_elewise_add_act_ops -> fused_bias_act +
        #                               fused_dropout_add_ln rewrites
        #   fuse_all_optimizer_ops   -> accepted and inert: XLA already
        #                               fuses each parameter's update
        #                               into one elementwise kernel
        # PADDLE_TPU_FUSION=0 kills the whole pipeline;
        # CompiledProgram.fusion_report() shows what fired and why not.
        self.fuse_all_reduce_ops = True
        self.fuse_elewise_add_act_ops = True
        self.fuse_all_optimizer_ops = True
        # TPU-native pattern families beyond the reference's flags:
        # attention subgraph -> Pallas flash kernel (gated on the
        # measured engagement threshold), softmax+cross_entropy -> one
        # numerically-stable op
        self.fuse_attention = True
        self.fuse_softmax_xent = True
        # reference fuse_bn_act_ops, extended to ride the conv too:
        # conv2d -> batch_norm -> (act) becomes one fused_conv_bn_act
        # (Pallas epilogue on TPU).  The gate weighs its predicted delta
        # by the autotune calibration factor (paddle_tpu.autotune) when
        # a silicon sweep recorded one.
        self.fuse_bn_act_ops = True
        self.enable_sequential_execution = False
        self.remove_unnecessary_lock = True
        self.num_trainers = 1
        self.trainer_id = 0
        self.trainers_endpoints = []
        # under jit+GSPMD batch-norm stats of a batch-sharded input are
        # ALWAYS global (the partitioner emits the cross-device reduction),
        # so DP batch norm is inherently synchronized — the reference's
        # sync_batch_norm_pass is subsumed; the knob is kept for API parity
        # (tests/test_grad_accum_syncbn.py proves the global-stats parity)
        self.sync_batch_norm = False
        self.nccl_comm_num = 1
        self.use_hierarchical_allreduce = False
        # TPU-native extensions
        # jax.checkpoint: honored by pipeline stages (parallel/pipeline.py)
        # and ring attention; the plain executor path warns (explicit grad
        # ops read named activations, so segment remat must be chosen at
        # the model level)
        self.remat = False
        # ZeRO-1: partition param-shaped optimizer accumulators (Adam
        # moments etc.) over the data axis — per-chip optimizer memory
        # drops by dp_degree (the fleet "sharding" strategy, TPU-style)
        self.shard_optimizer_state = False
        self.donate_params = True
        # microbatch gradient accumulation (reference
        # ir/multi_batch_merge_pass.cc "repeat"): split the batch into k
        # microbatches, scan fwd+bwd accumulating grads, apply the
        # optimizer once on the average
        self.batch_merge_repeat = 1
        # tensor parallelism (SURVEY §2.3 TP row — beyond the reference,
        # which only row-shards PS parameter blocks): devices reshape to a
        # (data, model) mesh and params annotated with
        # ParamAttr(shard_spec=...) partition over the model axis
        self.tensor_parallel_degree = 1


class ExecutionStrategy:
    def __init__(self):
        self.num_threads = 0
        self.num_iteration_per_drop_scope = 1
        self.num_iteration_per_run = 1
        self.use_thread_barrier = False


class CompiledProgram:
    def __init__(self, program_or_graph, build_strategy=None):
        self._program = program_or_graph
        self._build_strategy = build_strategy or BuildStrategy()
        self._is_data_parallel = False
        self._loss_name = None
        self._exec_strategy = None
        self._places = None
        self._share_vars_from = None
        self._parallel_runner = None
        self._last_fusion_report = None
        self._last_fusion_key = None

    def with_data_parallel(self, loss_name=None, build_strategy=None,
                           exec_strategy=None, share_vars_from=None,
                           places=None):
        self._is_data_parallel = True
        self._loss_name = loss_name
        if build_strategy is not None:
            self._build_strategy = build_strategy
        self._warn_inert_knobs(self._build_strategy)
        self._exec_strategy = exec_strategy or ExecutionStrategy()
        self._places = places
        self._share_vars_from = share_vars_from
        return self

    @staticmethod
    def _warn_inert_knobs(bs):
        """A user porting reference code must not get silently different
        behavior: warn for knobs this backend does not honor."""
        import warnings

        if bs.reduce_strategy != BuildStrategy.ReduceStrategy.AllReduce:
            warnings.warn(
                "BuildStrategy.reduce_strategy=Reduce has no TPU "
                "equivalent: GSPMD always emits fused all-reduce over ICI; "
                "proceeding with AllReduce semantics", stacklevel=3)
        if (bs.gradient_scale_strategy
                == BuildStrategy.GradientScaleStrategy.Customized):
            warnings.warn(
                "GradientScaleStrategy.Customized is not supported: scale "
                "the loss explicitly in the program instead "
                "(reference multi_devices_graph_pass ScaleLossGrad)",
                stacklevel=3)
        if getattr(bs, "remat", False):
            warnings.warn(
                "BuildStrategy.remat applies to pipeline stages "
                "(PipelineOptimizer) and ring attention only; for the "
                "plain executor pick recompute boundaries at the model "
                "level with `with fluid.layers.recompute():`",
                stacklevel=3)

    def with_inference_optimize(self, config):
        # analysis passes are XLA's job under jit; clone(for_test) is enough
        self._program = self._program.clone(for_test=True)
        return self

    @property
    def program(self):
        return self._program

    def fusion_report(self):
        """The fusion pipeline's outcome for this program under this
        BuildStrategy: applied rewrites with op coordinates and
        predicted deltas, plus matched-but-skipped patterns with the
        cost-model reason.  Resolves the fused program on demand if no
        run has happened yet (fetch-target protection then defaults to
        'nothing fetched')."""
        from .static_analysis import fusion as _fusion

        if self._parallel_runner is not None \
                and self._parallel_runner._last_fusion_report is not None:
            return self._parallel_runner._last_fusion_report
        if self._last_fusion_report is not None:
            return self._last_fusion_report
        _, report = _fusion.resolve_fused_program(
            self._program,
            config=_fusion.FusionConfig.from_build_strategy(
                self._build_strategy))
        return report

    def _run(self, executor, feed, fetch_list, scope, return_numpy):
        accum = getattr(self._build_strategy, "batch_merge_repeat", 1) or 1
        iters = int(getattr(self._exec_strategy, "num_iteration_per_run",
                            1) or 1) if self._exec_strategy else 1
        if not self._is_data_parallel and accum <= 1 and iters <= 1:
            # hand the BuildStrategy-derived fusion config to the
            # executor so the fuse_* flags are honored on the plain path
            # too — including when every pass no-ops (the executor must
            # not fall back to the default config and re-enable families
            # the strategy disabled)
            from .framework import Variable
            from .static_analysis import fusion as _fusion

            config = _fusion.FusionConfig.from_build_strategy(
                self._build_strategy)
            targets = [v.name if isinstance(v, Variable) else str(v)
                       for v in (fetch_list or [])]
            # refresh the report only when its resolve key changes —
            # steady-state steps skip the (cached) resolve entirely
            key = (config.signature(self._program), self._program._version,
                   tuple(sorted(set(targets))))
            if key != self._last_fusion_key:
                _, self._last_fusion_report = _fusion.resolve_fused_program(
                    self._program, config=config, targets=targets)
                self._last_fusion_key = key
            return executor.run(
                self._program, feed=feed, fetch_list=fetch_list,
                scope=scope, return_numpy=return_numpy,
                use_program_cache=True, _fusion_config=config,
            )
        from .parallel import SPMDRunner

        if self._parallel_runner is None:
            self._parallel_runner = SPMDRunner(
                self._program, self._build_strategy, self._places,
                data_parallel=self._is_data_parallel,
                exec_strategy=self._exec_strategy,
            )
        return self._parallel_runner.run(
            executor, feed, fetch_list, scope, return_numpy
        )
