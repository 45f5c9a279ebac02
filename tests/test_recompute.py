"""fluid.layers.recompute(): activation rematerialization as a
jax.checkpoint'd sub-block region (SURVEY §7g remat; beyond the v1.5
reference — later Paddle added RecomputeOptimizer for the same job).

Oracles: (1) losses/grad-trajectory identical with and without the
region over several optimizer steps; (2) the compiled train step's temp
memory drops when a deep stack is wrapped (the point of remat)."""

import contextlib

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.executor import Scope, scope_guard


WIDTH = 256
DEPTH = 6


def _build(use_recompute, seed=3, attention=False):
    """``attention``: the stack ends in a flash attention site (the 64
    rows of a batch as 2 heads of 128 positions)."""
    fluid.unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[64, WIDTH] if attention
                              else [WIDTH], dtype="float32",
                              append_batch_size=not attention)
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = x
        with (fluid.layers.recompute() if use_recompute
              else contextlib.nullcontext()):
            for _ in range(DEPTH):
                h = fluid.layers.fc(input=h, size=WIDTH, act="relu")
            if attention:
                q = fluid.layers.reshape(h, [1, 2, 128, 64])
                h = h + fluid.layers.reshape(
                    fluid.layers.fused_multihead_attention(q, q, q),
                    [64, WIDTH])
        pred = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.reduce_mean(
            fluid.layers.square_error_cost(input=pred, label=y))
        fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
    return main, startup, loss


def _feed(rng, batch=8):
    x = rng.randn(batch, WIDTH).astype("float32")
    return {"x": x, "y": (x.sum(1, keepdims=True) > 0).astype("float32")}


class TestRecompute:
    def test_loss_trajectory_identical(self):
        rng = np.random.RandomState(0)
        feed = _feed(rng)
        traj = {}
        for use in (False, True):
            main, startup, loss = _build(use)
            sc = Scope()
            with scope_guard(sc):
                exe = fluid.Executor(fluid.CPUPlace())
                exe.run(startup)
                traj[use] = [
                    float(np.asarray(
                        exe.run(main, feed=feed,
                                fetch_list=[loss])[0]).reshape(-1)[0])
                    for _ in range(6)]
        np.testing.assert_allclose(traj[False], traj[True],
                                   rtol=1e-5, atol=1e-7)
        assert traj[True][-1] < traj[True][0]

    @pytest.mark.parametrize("kernel_site", [False, True])
    def test_backward_recomputes_behind_barrier(self, kernel_site,
                                                monkeypatch):
        """Structural oracle: the lowered (pre-optimization) module must
        contain the region's EXTRA forward matmuls plus the
        optimization_barrier that roots them — byte-identical to what
        native jax.checkpoint emits.  (The XLA CPU backend then CSE's
        both away — verified against native jax.checkpoint, which shows
        the same temp bytes with and without remat on CPU — so a
        temp-size assertion is only meaningful on TPU, where the
        scheduler honors the barrier.)

        The ONE barrier ties everything the re-run reads to a gradient
        the backward pass made, so no re-run can start before the
        backward pass reaches its region (the scheduler had hoisted every
        region's re-run to the front: 15.16 against 11.48 GiB compiled,
        PERF.md PR 36).  ``kernel_site``: what a flash kernel site keeps
        of the region's forward run (``o, m, l``) crosses the same
        barrier, and the backward kernels read it behind it."""
        import jax
        import jax.numpy as jnp

        import paddle_tpu.executor as ex

        if kernel_site:
            monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
        rng = np.random.RandomState(1)
        feed = {k: jnp.asarray(v) for k, v in _feed(rng, batch=64).items()}
        dots = {}
        for use in (False, True):
            main, startup, loss = _build(use, attention=kernel_site)
            sc = Scope()
            with scope_guard(sc):
                exe = fluid.Executor(fluid.CPUPlace())
                exe.run(startup)
                cb = ex._CompiledBlock(main, main.global_block(),
                                       list(feed.keys()), [loss.name],
                                       sc, "train")
                rw = {n: sc.get(n) for n in cb.rw_names}
                ro = {n: sc.get(n) for n in cb.ro_names}
                traced = cb.jitted.trace(feed, rw, ro, ex.rng_key(0))
                txt = traced.lower().as_text()
                dots[use] = txt.count("stablehlo.dot_general")
        # the remat graph re-runs the DEPTH hidden matmuls in backward
        assert dots[True] >= dots[False] + DEPTH, dots

        eqns = traced.jaxpr.jaxpr.eqns
        made_at = {v: i for i, e in enumerate(eqns) for v in e.outvars}
        (barrier,) = [e for e in eqns
                      if e.primitive.name == "optimization_barrier"]
        loss_at = made_at[traced.jaxpr.jaxpr.outvars[0]]
        assert max(made_at.get(v, -1) for v in barrier.invars) > loss_at, (
            "the barrier must hold the region's incoming gradient")
        behind = {v: i for i, v in enumerate(barrier.outvars)}
        kernels = {e.params["name"]: e for e in eqns
                   if e.primitive.name == "pallas_call"}
        if not kernel_site:
            assert not kernels
            # DEPTH weights and biases, x, and the one incoming gradient
            assert len(barrier.invars) == 2 * DEPTH + 2
            return
        assert sorted(kernels) == ["flash_attention_dkv", "flash_attention_dq",
                                   "flash_attention_fwd"]
        assert len(barrier.invars) == 2 * DEPTH + 2 + 3
        kept = kernels["flash_attention_fwd"].outvars
        assert all(any(v is k for v in barrier.invars) for k in kept)
        m, l = (barrier.outvars[[v is k for v in barrier.invars].index(True)]
                for k in kept[1:])
        for name in ("flash_attention_dkv", "flash_attention_dq"):
            reads = kernels[name].invars
            assert any(v is m for v in reads) and any(v is l for v in reads)
            assert not any(v is k for v in reads for k in kept)
            # Q, K and V come from the re-run, behind the barrier
            assert all(made_at.get(v, -1) > made_at[barrier.outvars[0]]
                       or v in behind for v in reads)

    def test_multi_region_all_params_train(self):
        """Regression: the region op must DECLARE its captures as formal
        inputs — an inputless op orphans everything upstream from the
        op-path pruning, so earlier regions' params silently got no grad
        ops (found by a 3-region DP drive)."""
        fluid.unique_name.switch()
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = 7
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[32], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            h = x
            for _ in range(3):
                with fluid.layers.recompute():
                    h = fluid.layers.fc(input=h, size=32, act="relu")
            pred = fluid.layers.fc(input=h, size=1)
            loss = fluid.layers.reduce_mean(
                fluid.layers.square_error_cost(input=pred, label=y))
            fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
        block = main.global_block()
        n_grad = sum(1 for op in block.ops
                     if op.type == "recompute_block_grad")
        assert n_grad == 3, "every region needs a grad op, got %d" % n_grad
        n_sgd = sum(1 for op in block.ops if op.type == "sgd")
        assert n_sgd == 8, "all 4 fc layers' params update, got %d" % n_sgd
        rng = np.random.RandomState(4)
        xb = rng.randn(8, 32).astype("float32")
        feed = {"x": xb,
                "y": (xb.sum(1, keepdims=True) > 0).astype("float32")}
        sc = Scope()
        with scope_guard(sc):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            ls = [float(np.asarray(
                exe.run(main, feed=feed,
                        fetch_list=[loss])[0]).reshape(-1)[0])
                  for _ in range(8)]
        assert ls[-1] < ls[0] * 0.9, ls

    def test_clone_and_inference_export(self, tmp_path):
        """Train-with-recompute → clone(for_test) eval → inference-model
        round-trip: the sub-block must survive pruning + serialization."""
        fluid.unique_name.switch()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[16], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            with fluid.layers.recompute():
                h = fluid.layers.fc(input=x, size=32, act="relu")
            pred = fluid.layers.fc(input=h, size=1)
            loss = fluid.layers.reduce_mean(
                fluid.layers.square_error_cost(input=pred, label=y))
            test_prog = main.clone(for_test=True)
            fluid.optimizer.Adam(learning_rate=1e-2).minimize(loss)
        sc = Scope()
        rng = np.random.RandomState(0)
        xb = rng.randn(8, 16).astype("float32")
        feed = {"x": xb,
                "y": (xb.sum(1, keepdims=True) > 0).astype("float32")}
        with scope_guard(sc):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            exe.run(main, feed=feed, fetch_list=[loss])
            ev = exe.run(test_prog, feed=feed, fetch_list=[loss])[0]
            assert np.isfinite(np.asarray(ev)).all()
            d = str(tmp_path)
            fluid.io.save_inference_model(d, ["x"], [pred], exe,
                                          main_program=main)
            prog2, fnames, ftargets = fluid.io.load_inference_model(d, exe)
            o = exe.run(prog2, feed={fnames[0]: xb},
                        fetch_list=ftargets)[0]
            assert np.asarray(o).shape == (8, 1)

    def test_dropout_inside_region(self):
        """Per-op deterministic keys: the recomputed forward must draw
        the SAME dropout mask, so training stays stable and finite."""
        fluid.unique_name.switch()
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = 11
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[32], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            with fluid.layers.recompute():
                h = fluid.layers.fc(input=x, size=64, act="relu")
                h = fluid.layers.dropout(
                    h, 0.3, dropout_implementation="upscale_in_train")
            pred = fluid.layers.fc(input=h, size=1)
            loss = fluid.layers.reduce_mean(
                fluid.layers.square_error_cost(input=pred, label=y))
            fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
        rng = np.random.RandomState(2)
        xb = rng.randn(8, 32).astype("float32")
        feed = {"x": xb,
                "y": (xb.sum(1, keepdims=True) > 0).astype("float32")}
        sc = Scope()
        with scope_guard(sc):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            ls = [float(np.asarray(
                exe.run(main, feed=feed,
                        fetch_list=[loss])[0]).reshape(-1)[0])
                  for _ in range(8)]
        assert all(np.isfinite(ls)), ls
        assert ls[-1] < ls[0], ls


class TestRecomputeOptimizer:
    """fluid.optimizer.RecomputeOptimizer (the fleet use_recompute
    contract): post-hoc rewrite at the checkpoint vars — interior
    segments become recompute_block regions, training is numerically
    identical to the unwrapped program."""

    def _build(self, seed=33):
        fluid.unique_name.switch()
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = seed
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[32], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            h1 = fluid.layers.fc(input=x, size=64, act="relu")
            h2 = fluid.layers.fc(input=h1, size=64, act="relu")
            h3 = fluid.layers.fc(input=h2, size=32, act="relu")
            pred = fluid.layers.fc(input=h3, size=1)
            loss = fluid.layers.reduce_mean(
                fluid.layers.square_error_cost(input=pred, label=y))
        return main, startup, loss, [h1, h2]

    def _train(self, wrap, steps=8):
        main, startup, loss, cps = self._build()
        with fluid.program_guard(main, startup):
            opt = fluid.optimizer.SGD(learning_rate=0.05)
            if wrap:
                opt = fluid.optimizer.RecomputeOptimizer(opt)
                opt._set_checkpoints(cps)
            opt.minimize(loss)
        rng = np.random.RandomState(3)
        xb = rng.randn(8, 32).astype("float32")
        feed = {"x": xb,
                "y": (xb.sum(1, keepdims=True) > 0).astype("float32")}
        sc = Scope()
        with scope_guard(sc):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            ls = [float(np.asarray(
                exe.run(main, feed=feed,
                        fetch_list=[loss])[0]).reshape(-1)[0])
                  for _ in range(steps)]
        return main, ls

    def test_rewrite_structure(self):
        main, ls = self._train(wrap=True, steps=1)
        types = [op.type for op in main.global_block().ops]
        # two interior segments wrapped (up to h1, h1->h2); the tail
        # (h2 -> loss) stays unwrapped
        assert types.count("recompute_block") == 2
        # forward compute ops for h1/h2 moved out of block 0
        assert types.count("relu") == 1  # only h3's tail relu remains

    def test_loss_trajectory_identical(self):
        _, plain = self._train(wrap=False)
        _, wrapped = self._train(wrap=True)
        np.testing.assert_allclose(wrapped, plain, rtol=1e-6, atol=1e-7)
        assert plain[-1] < plain[0]

    def test_requires_checkpoints_and_pre_backward(self):
        import pytest

        main, startup, loss, cps = self._build()
        with fluid.program_guard(main, startup):
            opt = fluid.optimizer.RecomputeOptimizer(
                fluid.optimizer.SGD(learning_rate=0.05))
            with pytest.raises(ValueError):
                opt.minimize(loss)
            opt._set_checkpoints([cps[0]])
            opt.minimize(loss)
            # a second rewrite after backward must refuse
            from paddle_tpu.optimizer import rewrite_program_recompute

            with pytest.raises(RuntimeError):
                rewrite_program_recompute(main, [cps[1].name])

    def test_fleet_strategy_wires_recompute(self):
        from paddle_tpu.incubate.fleet.base.role_maker import (
            Role, UserDefinedRoleMaker)
        from paddle_tpu.incubate.fleet.collective import (
            CollectiveOptimizer, DistributedStrategy, fleet)

        fleet.init(UserDefinedRoleMaker(current_id=0, role=Role.WORKER,
                                        worker_num=1))
        main, startup, loss, cps = self._build()
        strategy = DistributedStrategy()
        strategy.use_recompute = True
        strategy.recompute_checkpoints = [c.name for c in cps]
        with fluid.program_guard(main, startup):
            opt = fleet.distributed_optimizer(
                fluid.optimizer.SGD(learning_rate=0.05), strategy)
            opt.minimize(loss)
        types = [op.type for op in main.global_block().ops]
        assert types.count("recompute_block") == 2


class TestRecomputeComposition:
    def test_amp_plus_recompute_casts_inside_regions(self):
        """fleet use_amp + use_recompute: AMP sits OUTERMOST so the bf16
        rewrite runs before segments move — the recompute sub-blocks
        must contain cast ops (previously the wrapped body silently
        stayed fp32)."""
        from paddle_tpu.incubate.fleet.collective import (
            CollectiveOptimizer, DistributedStrategy)

        fluid.unique_name.switch()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[32], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            h1 = fluid.layers.fc(input=x, size=64, act="relu")
            h2 = fluid.layers.fc(input=h1, size=64, act="relu")
            pred = fluid.layers.fc(input=h2, size=1)
            loss = fluid.layers.reduce_mean(
                fluid.layers.square_error_cost(input=pred, label=y))
            strategy = DistributedStrategy()
            strategy.use_amp = True
            strategy.use_recompute = True
            strategy.recompute_checkpoints = [h1.name]
            opt = CollectiveOptimizer(
                fluid.optimizer.SGD(learning_rate=0.05), strategy)
            opt.minimize(loss, startup_program=startup)
        types0 = [op.type for op in main.global_block().ops]
        assert "recompute_block" in types0
        rc = next(op for op in main.global_block().ops
                  if op.type == "recompute_block")
        sub = main.blocks[rc.attrs["sub_block"]]
        sub_types = [op.type for op in sub.ops]
        assert "cast" in sub_types, sub_types  # bf16 AMP reached inside
        # and the program still trains
        from paddle_tpu.executor import Scope, scope_guard

        rng = np.random.RandomState(1)
        xb = rng.randn(8, 32).astype("float32")
        feed = {"x": xb,
                "y": (xb.sum(1, keepdims=True) > 0).astype("float32")}
        with scope_guard(Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            ls = [float(np.asarray(exe.run(
                main, feed=feed, fetch_list=[loss])[0]).reshape(-1)[0])
                for _ in range(6)]
        assert all(np.isfinite(ls)) and ls[-1] < ls[0], ls

    def test_repeat_minimize_does_not_stack_wrappers(self):
        """Two minimize() calls (train + a second program) must not
        stack AMP/recompute wrappers or leak first-call checkpoints."""
        from paddle_tpu.incubate.fleet.collective import (
            CollectiveOptimizer, DistributedStrategy)

        def build():
            fluid.unique_name.switch()
            main, startup = fluid.Program(), fluid.Program()
            with fluid.program_guard(main, startup):
                x = fluid.layers.data(name="x", shape=[8],
                                      dtype="float32")
                h = fluid.layers.fc(input=x, size=8, act="relu")
                pred = fluid.layers.fc(input=h, size=1)
                loss = fluid.layers.reduce_mean(
                    fluid.layers.square(pred))
            return main, startup, loss, h

        strategy = DistributedStrategy()
        strategy.use_recompute = True
        inner = fluid.optimizer.SGD(learning_rate=0.05)
        opt = CollectiveOptimizer(inner, strategy)

        main1, startup1, loss1, h1 = build()
        strategy.recompute_checkpoints = [h1.name]
        with fluid.program_guard(main1, startup1):
            opt.minimize(loss1, startup_program=startup1)
        assert opt._optimizer is inner  # no wrapper stacking

        main2, startup2, loss2, h2 = build()
        strategy.recompute_checkpoints = [h2.name]  # fresh checkpoints
        with fluid.program_guard(main2, startup2):
            opt.minimize(loss2, startup_program=startup2)
        for prog in (main1, main2):
            types = [op.type for op in prog.global_block().ops]
            assert types.count("recompute_block") == 1

    def test_decomposed_backward_applies_rewrite(self):
        """The API.spec backward()/apply_gradients() decomposition must
        recompute too (previously backward() silently skipped the
        rewrite)."""
        fluid.unique_name.switch()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[8], dtype="float32")
            h = fluid.layers.fc(input=x, size=8, act="relu")
            pred = fluid.layers.fc(input=h, size=1)
            loss = fluid.layers.reduce_mean(fluid.layers.square(pred))
            opt = fluid.optimizer.RecomputeOptimizer(
                fluid.optimizer.SGD(learning_rate=0.05))
            opt._set_checkpoints([h])
            pg = opt.backward(loss)
            opt.apply_gradients(pg)
        types = [op.type for op in main.global_block().ops]
        assert "recompute_block" in types
        assert any(t == "sgd" for t in types)
