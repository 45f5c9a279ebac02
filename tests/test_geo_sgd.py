"""Geo-SGD (reference ``distribute_transpiler.py:131`` geo fields + the
geo ``Communicator`` mode): k-step local training with periodic
delta-averaging, redesigned as a gated delta-allreduce
(``transpiler/collective.py`` GeoSGD).

Two oracles:
1. shard_map 2-worker run of the transpiled op tail with a REAL psum —
   diverged workers must converge to the delta-average exactly on sync
   steps and stay untouched on local steps.
2. executor-level config-driven parity: under GSPMD (identity
   collectives) a geo-transpiled program must train bit-identically to
   the untranspiled baseline.
"""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.executor import Scope, scope_guard, _run_ops_into_env
from paddle_tpu.ops import registry as op_registry


def _build_geo_program(k, nranks):
    fluid.unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        w = fluid.layers.create_parameter([4], "float32", name="w")
    cfg = fluid.DistributeTranspilerConfig()
    cfg.geo_sgd_mode = True
    cfg.geo_sgd_need_push_nums = k
    t = fluid.DistributeTranspiler(config=cfg)
    t.transpile(trainer_id=0, program=main, startup_program=startup,
                trainers=nranks)
    return main, startup


class TestGeoDeltaAverageUnderPsum:
    def _run_tail(self, main, w_vals, snap_vals, step_val):
        """Run the transpiled block ops under shard_map(2 workers) with a
        real psum (ctx.collective_axis)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from paddle_tpu.jax_compat import shard_map

        mesh = Mesh(np.array(jax.devices()[:2]), ("workers",))
        block = main.global_block()

        def per_worker(w, snap, step):
            ctx = op_registry.LoweringContext(mode="train")
            ctx.collective_axis = "workers"
            env = {"w": w[0], "w@GEO_SNAPSHOT": snap[0],
                   "geo_sgd@STEP": step[0]}
            _run_ops_into_env(block, env, ctx)
            return (env["w"][None], env["w@GEO_SNAPSHOT"][None],
                    env["geo_sgd@STEP"][None])

        f = shard_map(
            per_worker, mesh=mesh,
            in_specs=(P("workers"), P("workers"), P("workers")),
            out_specs=(P("workers"), P("workers"), P("workers")))
        return [np.asarray(v) for v in f(
            jnp.asarray(w_vals), jnp.asarray(snap_vals),
            jnp.asarray(step_val))]

    def test_sync_and_local_steps(self):
        main, _ = _build_geo_program(k=2, nranks=2)
        snap = np.tile(np.arange(4, dtype="float32"), (2, 1))  # both [0,1,2,3]
        w = snap + np.array([[1.0], [3.0]], "float32")  # deltas -1 and -3

        # counter 0 → increments to 1 → 1 % 2 != 0 → LOCAL step: untouched
        w1, s1, st1 = self._run_tail(main, w, snap, np.zeros((2, 1), "f4"))
        np.testing.assert_allclose(w1, w)
        np.testing.assert_allclose(s1, snap)

        # counter 1 → increments to 2 → sync: delta=snap-w per worker
        # (-1, -3), mean -2 → w = snap + 2 on BOTH; snapshot = new w
        w2, s2, st2 = self._run_tail(main, w1, s1, st1)
        np.testing.assert_allclose(w2, snap + 2.0)
        np.testing.assert_allclose(s2, w2)


class TestGeoConfigParity:
    def _train(self, geo, steps=5):
        fluid.unique_name.switch()
        rng = np.random.RandomState(0)
        xs = rng.randn(steps, 8, 4).astype("float32")
        ys = rng.randn(steps, 8, 1).astype("float32")
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=[8, 4], dtype="float32",
                                  append_batch_size=False)
            y = fluid.layers.data("y", shape=[8, 1], dtype="float32",
                                  append_batch_size=False)
            pred = fluid.layers.fc(x, size=1,
                                   param_attr=fluid.ParamAttr(name="fc.w"))
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(pred, y))
            fluid.optimizer.SGD(0.1).minimize(loss)
        if geo:
            cfg = fluid.DistributeTranspilerConfig()
            cfg.geo_sgd_mode = True
            cfg.geo_sgd_need_push_nums = 2
            t = fluid.DistributeTranspiler(config=cfg)
            t.transpile(trainer_id=0, program=main,
                        startup_program=startup, trainers=2)
        exe = fluid.Executor(fluid.CPUPlace())
        losses = []
        with scope_guard(Scope()):
            exe.run(startup)
            for i in range(steps):
                (lv,) = exe.run(main, feed={"x": xs[i], "y": ys[i]},
                                fetch_list=[loss])
                losses.append(float(np.asarray(lv).reshape(())))
        return losses

    def test_identity_collective_parity(self):
        """Single-process GSPMD: the allreduce is identity, so geo must
        reproduce baseline training exactly (gated ops must not perturb
        params on either local or sync steps)."""
        base = self._train(geo=False)
        geo = self._train(geo=True)
        np.testing.assert_allclose(geo, base, rtol=1e-6)
