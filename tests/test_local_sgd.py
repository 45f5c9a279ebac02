"""LocalSGD (reference ``transpiler/collective.py:263``): snapshot
params at sync, train locally, allreduce the parameter DELTAS.  Wired
through ``DistributeTranspiler(mode='local_sgd')`` and the fleet
``DistributedStrategy.use_local_sgd`` knob."""

import numpy as np

import paddle_tpu as fluid
from paddle_tpu.executor import Scope, scope_guard


def _build(lr=0.05, seed=9):
    fluid.unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[8], dtype="float32")
        y = fluid.layers.data("y", shape=[1], dtype="float32")
        h = fluid.layers.fc(x, size=16, act="relu")
        pred = fluid.layers.fc(h, size=1)
        loss = fluid.layers.reduce_mean(
            fluid.layers.square_error_cost(input=pred, label=y))
        fluid.optimizer.SGD(learning_rate=lr).minimize(loss)
    return main, startup, loss


def _batches(n=6, bs=16):
    r = np.random.RandomState(5)
    out = []
    for _ in range(n):
        xb = r.randn(bs, 8).astype("float32")
        out.append({"x": xb,
                    "y": (xb.sum(1, keepdims=True) > 0).astype(
                        "float32")})
    return out


def _train(prog, startup, loss, dp):
    exe = fluid.Executor(fluid.CPUPlace())
    sc = Scope()
    with scope_guard(sc):
        exe.run(startup)
        run = prog
        if dp:
            run = fluid.CompiledProgram(prog).with_data_parallel(
                loss_name=loss.name)
        ls = [float(np.asarray(exe.run(run, feed=f,
                                       fetch_list=[loss])[0])
                    .reshape(-1)[0]) for f in _batches()]
    return ls


def test_transpile_structure_and_training():
    """mode='local_sgd' inserts per-param snapshot/delta/allreduce/
    restore chains after the optimizer, snapshots init in startup, and
    the program still trains (single-process GSPMD: the delta
    allreduce is consistency-preserving)."""
    main, startup, loss = _build()
    t = fluid.DistributeTranspiler()
    t.config.mode = "local_sgd"
    t.transpile(trainer_id=0, program=main, trainers=2,
                startup_program=startup)
    types = [op.type for op in main.global_block().ops]
    # 4 params (2 w + 2 b): each gets sub, allreduce, sub, assign
    assert types.count("c_allreduce_sum") == 4
    assert types.count("assign") >= 4
    snap_inits = [op for op in startup.global_block().ops
                  if op.type == "assign"]
    assert len(snap_inits) == 4
    assert any(n.endswith("@SNAPSHOT")
               for n in main.global_block().vars)
    # allreduce pre-scales by 1/nranks
    ar = next(op for op in main.global_block().ops
              if op.type == "c_allreduce_sum")
    assert abs(ar.attrs["pre_scale"] - 0.5) < 1e-9
    ls = _train(main, startup, loss, dp=True)
    assert all(np.isfinite(ls))
    assert ls[-1] < ls[0], ls


def test_local_sgd_single_process_matches_plain():
    """Under single-process GSPMD the delta-allreduce averages
    identical replicas — training equals the plain program."""
    main, startup, loss = _build()
    plain = _train(main, startup, loss, dp=True)
    main2, startup2, loss2 = _build()
    t = fluid.DistributeTranspiler()
    t.config.mode = "local_sgd"
    t.transpile(trainer_id=0, program=main2, trainers=2,
                startup_program=startup2)
    wrapped = _train(main2, startup2, loss2, dp=True)
    np.testing.assert_allclose(wrapped, plain, rtol=1e-5, atol=1e-6)


def test_fleet_use_local_sgd_knob():
    """The strategy knob routes through CollectiveOptimizer; with 2
    trainers recorded, the local-SGD chain is inserted (worker_num=1 is
    a clean no-op — LocalSGD skips for nranks<=1)."""
    from paddle_tpu.incubate.fleet.collective import (
        CollectiveOptimizer, DistributedStrategy)

    fluid.unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[8], dtype="float32")
        y = fluid.layers.data("y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(x, size=1)
        loss = fluid.layers.reduce_mean(
            fluid.layers.square_error_cost(input=pred, label=y))
        main._num_trainers = 2  # topology as a 2-worker fleet records it
        strategy = DistributedStrategy()
        strategy.use_local_sgd = True
        opt = CollectiveOptimizer(
            fluid.optimizer.SGD(learning_rate=0.05), strategy)
        opt.minimize(loss, startup_program=startup)
    types = [op.type for op in main.global_block().ops]
    assert "c_allreduce_sum" in types
    assert any(n.endswith("@SNAPSHOT")
               for n in main.global_block().vars)


class TestLocalSGDDeltaAverageUnderPsum:
    """shard_map 2-worker oracle (the geo-SGD test's pattern): diverged
    workers must land on the delta-average after the LocalSGD tail runs
    with a REAL psum."""

    def test_diverged_workers_average(self):
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from paddle_tpu.jax_compat import shard_map
        import jax

        from paddle_tpu.executor import _run_ops_into_env
        from paddle_tpu.ops import registry as op_registry
        from paddle_tpu.transpiler.collective import LocalSGD

        fluid.unique_name.switch()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            fluid.layers.create_parameter([4], "float32", name="w")
        LocalSGD().transpile(program=main, startup_program=startup,
                             rank=0, nranks=2)
        block = main.global_block()
        mesh = Mesh(np.array(jax.devices()[:2]), ("workers",))

        def per_worker(w, snap):
            ctx = op_registry.LoweringContext(mode="train")
            ctx.collective_axis = "workers"
            env = {"w": w[0], "w@SNAPSHOT": snap[0]}
            _run_ops_into_env(block, env, ctx)
            return env["w"][None], env["w@SNAPSHOT"][None]

        f = shard_map(per_worker, mesh=mesh,
                      in_specs=(P("workers"), P("workers")),
                      out_specs=(P("workers"), P("workers")))
        snap = np.tile(np.arange(4, dtype="float32"), (2, 1))
        # locally-trained params drifted by -1 and -3 from the snapshot
        w = snap - np.array([[1.0], [3.0]], "float32")
        w2, s2 = (np.asarray(v) for v in
                  f(jnp.asarray(w), jnp.asarray(snap)))
        # delta = snap - w = (+1, +3); mean 2 → w = snap - 2 on BOTH
        np.testing.assert_allclose(w2, snap - 2.0)
        # snapshot re-arms to the synced params
        np.testing.assert_allclose(s2, w2)
