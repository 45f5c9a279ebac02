"""Inference stack tests (reference: inference/tests/api/*,
unittests/test_inference_model_io.py, test_inference_transpiler.py —
save → load → predict round-trips and pass-preserves-output checks)."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.executor import Scope, scope_guard
from paddle_tpu.inference import (
    AnalysisConfig, create_paddle_predictor, fuse_conv_bn,
    InferenceTranspiler)


def _build_convbn_model():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data("img", shape=[3, 8, 8], dtype="float32")
        conv = fluid.layers.conv2d(img, num_filters=4, filter_size=3,
                                   padding=1)
        bn = fluid.layers.batch_norm(conv, act="relu")
        conv2 = fluid.layers.conv2d(bn, num_filters=4, filter_size=3,
                                    padding=1)
        bn2 = fluid.layers.batch_norm(conv2)
        pool = fluid.layers.pool2d(bn2, pool_size=8, pool_type="avg")
        logits = fluid.layers.fc(pool, size=3)
        label = fluid.layers.data("label", shape=[1], dtype="int64")
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        test_prog = main.clone(for_test=True)
        fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    return main, startup, test_prog, img, label, logits, loss


class TestFuseConvBn:
    def test_fold_preserves_output(self):
        main, startup, test_prog, img, label, logits, loss = \
            _build_convbn_model()
        exe = fluid.Executor(fluid.CPUPlace())
        rng = np.random.RandomState(0)
        x = rng.rand(2, 3, 8, 8).astype("float32")
        y = rng.randint(0, 3, size=(2, 1)).astype("int64")
        scope = Scope()
        with scope_guard(scope):
            exe.run(startup)
            # a few steps so bn stats are non-trivial
            for _ in range(5):
                exe.run(main, feed={"img": x, "label": y},
                        fetch_list=[loss])
            (before,) = exe.run(test_prog, feed={"img": x, "label": y},
                                fetch_list=[logits])
            n_bn = sum(op.type == "batch_norm"
                       for op in test_prog.global_block().ops)
            assert n_bn == 2
            fused = fuse_conv_bn(test_prog, scope)
            assert fused == 2
            assert not any(op.type == "batch_norm"
                           for op in test_prog.global_block().ops)
            (after,) = exe.run(test_prog, feed={"img": x, "label": y},
                               fetch_list=[logits])
        np.testing.assert_allclose(before, after, rtol=1e-4, atol=1e-5)

    def test_transpiler_surface(self):
        main, startup, test_prog, img, label, logits, loss = \
            _build_convbn_model()
        exe = fluid.Executor(fluid.CPUPlace())
        scope = Scope()
        with scope_guard(scope):
            exe.run(startup)
            InferenceTranspiler().transpile(test_prog, fluid.CPUPlace(),
                                            scope)
        assert not any(op.type == "batch_norm"
                       for op in test_prog.global_block().ops)


class TestAnalysisPredictor:
    def test_save_load_predict(self, tmp_path):
        main, startup, test_prog, img, label, logits, loss = \
            _build_convbn_model()
        exe = fluid.Executor(fluid.CPUPlace())
        rng = np.random.RandomState(1)
        x = rng.rand(2, 3, 8, 8).astype("float32")
        y = rng.randint(0, 3, size=(2, 1)).astype("int64")
        model_dir = str(tmp_path / "model")
        with scope_guard(Scope()):
            exe.run(startup)
            for _ in range(3):
                exe.run(main, feed={"img": x, "label": y},
                        fetch_list=[loss])
            (expect,) = exe.run(test_prog, feed={"img": x, "label": y},
                                fetch_list=[logits])
            fluid.io.save_inference_model(
                model_dir, ["img"], [logits], exe, main_program=test_prog)

        for ir_optim in (False, True):
            config = AnalysisConfig(model_dir)
            config.switch_ir_optim(ir_optim)
            pred = create_paddle_predictor(config)
            assert pred.get_input_names() == ["img"]
            (got,) = pred.run([x])
            np.testing.assert_allclose(got, expect, rtol=1e-4, atol=1e-5)
            has_bn = any(op.type == "batch_norm"
                         for op in pred.program.global_block().ops)
            assert has_bn == (not ir_optim)

    def test_predictors_isolated(self, tmp_path):
        """Two predictors own separate scopes (reference: per-predictor
        sub-scope in analysis_predictor.cc)."""
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=[4], dtype="float32")
            out = fluid.layers.fc(x, size=2)
        exe = fluid.Executor(fluid.CPUPlace())
        d = str(tmp_path / "m")
        with scope_guard(Scope()):
            exe.run(startup)
            fluid.io.save_inference_model(d, ["x"], [out], exe,
                                          main_program=main)
        p1 = create_paddle_predictor(AnalysisConfig(d))
        p2 = create_paddle_predictor(AnalysisConfig(d))
        xv = np.ones((1, 4), "float32")
        r1 = p1.run([xv])[0]
        # clobber p2's params; p1 must be unaffected
        p2._scope.set(p2.program.all_parameters()[0].name,
                      np.zeros_like(p2._scope.get(
                          p2.program.all_parameters()[0].name)))
        r1b = p1.run([xv])[0]
        np.testing.assert_array_equal(r1, r1b)


def test_fc_fuse_and_dce_passes():
    """fc_fuse_pass folds mul+add(bias) into one fc op; DCE prunes ops
    off the target path; outputs unchanged (reference
    ir/fc_fuse_pass.cc + analysis memory passes)."""
    from paddle_tpu.analysis import Analyzer, PassBuilder

    rng = np.random.RandomState(0)
    fluid.unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4], dtype="float32")
        h = fluid.layers.fc(x, size=8, act=None)      # mul + add
        dead = fluid.layers.fc(x, size=16)            # not on target path
        out = fluid.layers.fc(h, size=3)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = Scope()
    with scope_guard(scope):
        exe.run(startup)
        xv = rng.randn(5, 4).astype("float32")
        before = exe.run(main, feed={"x": xv}, fetch_list=[out])[0]
        n_ops_before = len(main.global_block().ops)
        Analyzer(PassBuilder(["fc_fuse_pass",
                              "dead_code_elimination_pass"])).run(
            main, scope=scope, targets=[out.name])
        n_ops_after = len(main.global_block().ops)
        after = exe.run(main, feed={"x": xv}, fetch_list=[out])[0]
    assert n_ops_after < n_ops_before
    types = [op.type for op in main.global_block().ops]
    assert "fc" in types and "elementwise_add" not in types
    # the dead fc's mul is gone
    assert types.count("mul") == 0
    np.testing.assert_allclose(after, before, rtol=1e-5, atol=1e-6)


def test_predictor_runs_analysis_pipeline(tmp_path):
    rng = np.random.RandomState(1)
    fluid.unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data("img", shape=[3, 8, 8], dtype="float32")
        c = fluid.layers.conv2d(img, num_filters=4, filter_size=3,
                                padding=1, bias_attr=False)
        c = fluid.layers.batch_norm(c)
        p = fluid.layers.pool2d(c, pool_size=8, pool_type="avg")
        out = fluid.layers.fc(p, size=2)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = Scope()
    with scope_guard(scope):
        exe.run(startup)
        xv = rng.randn(2, 3, 8, 8).astype("float32")
        # oracle must run BN in inference mode (moving stats), like the
        # exported model does
        test_prog = main.clone(for_test=True)
        ref = exe.run(test_prog, feed={"img": xv}, fetch_list=[out])[0]
        fluid.io.save_inference_model(
            str(tmp_path), ["img"], [out], exe, main)
    cfg = fluid.inference.AnalysisConfig(model_dir=str(tmp_path))
    pred = fluid.inference.create_paddle_predictor(cfg)
    got = pred.run([xv])[0]
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)
    types = [op.type for op in pred.program.global_block().ops]
    assert "batch_norm" not in types  # folded
    assert "fc" in types              # fused


def test_fc_fuse_preserves_fetched_intermediate():
    """Regression: fusing must not erase a var that is itself a target."""
    from paddle_tpu.analysis import Analyzer, PassBuilder

    fluid.unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4], dtype="float32")
        h = fluid.layers.fc(x, size=8)
    block = main.global_block()
    mul_out = next(op.outputs["Out"][0] for op in block.ops
                   if op.type == "mul")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = Scope()
    with scope_guard(scope):
        exe.run(startup)
        Analyzer(PassBuilder(["fc_fuse_pass"])).run(
            main, scope=scope, targets=[mul_out, h.name])
        xv = np.ones((2, 4), "float32")
        outs = exe.run(main, feed={"x": xv}, fetch_list=[mul_out, h])
    assert all(np.isfinite(o).all() for o in outs)
    types = [op.type for op in main.global_block().ops]
    assert "mul" in types  # fusion skipped, target still produced


def test_gn_resize_model_inference_roundtrip(tmp_path):
    """Round-4 layers survive the inference export: a GN + resize vision
    net saves via save_inference_model, reloads through the
    AnalysisPredictor pipeline, and reproduces its outputs exactly."""
    rng = np.random.RandomState(0)
    fluid.unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data("img", shape=[3, 8, 8], dtype="float32")
        up = fluid.layers.resize_bilinear(img, out_shape=[16, 16])
        conv = fluid.layers.conv2d(up, 4, 3, padding=1)
        gn = fluid.layers.group_norm(conv, groups=2, act="relu")
        pool = fluid.layers.pool2d(gn, 2, global_pooling=True)
        out = fluid.layers.fc(pool, size=3)
    exe = fluid.Executor(fluid.CPUPlace())
    d = str(tmp_path / "gn_model")
    xv = rng.randn(2, 3, 8, 8).astype("float32")
    with scope_guard(Scope()):
        exe.run(startup)
        (direct,) = exe.run(main, feed={"img": xv}, fetch_list=[out])
        fluid.io.save_inference_model(d, ["img"], [out], exe,
                                      main_program=main)
    with scope_guard(Scope()):
        prog, feeds, fetches = fluid.io.load_inference_model(d, exe)
        (loaded,) = exe.run(prog, feed={feeds[0]: xv},
                            fetch_list=fetches)
    np.testing.assert_allclose(loaded, direct, rtol=1e-5)

    cfg = AnalysisConfig(d)
    predictor = create_paddle_predictor(cfg)
    (pred_out,) = predictor.run({"img": xv})
    # predictor may run on the TPU while `direct` came from CPU: same
    # tolerance as test_predictor_runs_analysis_pipeline
    np.testing.assert_allclose(np.asarray(pred_out), direct, rtol=1e-4,
                               atol=1e-5)


def test_predictor_run_return_numpy_false(tmp_path):
    """return_numpy=False returns device arrays without a host sync —
    the serving-style pipelining contract bench.py's inference
    benchmark relies on (block once at the end)."""
    import jax

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4], dtype="float32")
        out = fluid.layers.fc(x, size=3)
    exe = fluid.Executor(fluid.CPUPlace())
    d = str(tmp_path / "m")
    with scope_guard(Scope()):
        exe.run(startup)
        fluid.io.save_inference_model(d, ["x"], [out], exe,
                                      main_program=main)
    pred = create_paddle_predictor(AnalysisConfig(d))
    xv = np.arange(8, dtype="float32").reshape(2, 4)
    outs = [pred.run([xv], return_numpy=False) for _ in range(3)]
    jax.block_until_ready(outs)
    (ref,) = pred.run([xv])
    for o in outs:
        assert not isinstance(o[0], np.ndarray)
        np.testing.assert_allclose(np.asarray(o[0]), ref, rtol=1e-6)


def test_analysis_config_enable_bf16_after_fold(tmp_path):
    """enable_bf16 rewrites AFTER the analysis passes: conv+bn folding
    must see the clean conv->bn producer chain (a pre-export bf16
    rewrite would cast-sandwich every bn and defeat the fold — the
    bench.py inference-headline bug this switch exists to prevent)."""
    from paddle_tpu.models.resnet import resnet_cifar10

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data("img", shape=[3, 32, 32],
                                dtype="float32")
        logits = resnet_cifar10(img, 10, 20, is_test=True)
    d = str(tmp_path / "m")
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(Scope()):
        exe.run(startup)
        fluid.io.save_inference_model(d, ["img"], [logits], exe,
                                      main_program=main)
    x = np.random.RandomState(0).randn(2, 3, 32, 32).astype("float32")

    ref_pred = create_paddle_predictor(AnalysisConfig(d))
    (ref,) = ref_pred.run([x])

    cfg = AnalysisConfig(d)
    cfg.enable_bf16()
    pred = create_paddle_predictor(cfg)
    ops = [op.type for op in pred.program.global_block().ops]
    assert ops.count("batch_norm") == 0, "fold defeated by bf16 casts"
    assert ops.count("cast") > 0, "bf16 rewrite missing"
    (got,) = pred.run([x])
    # bf16 numerics, scale-relative: error accumulates over 20 bf16
    # conv layers (near-zero logit elements make elementwise-relative
    # meaningless) — far outside fp32 noise (proves the bf16 graph
    # actually executed), far inside correctness tolerance
    err = np.abs(got.astype("float32") - ref).max() / np.abs(ref).max()
    assert 1e-6 < err < 0.05, err


def test_conv_bn_fold_nhwc(tmp_path):
    """The conv+bn fold handles channels-last: filter scaling is
    layout-independent (OIHW per output channel), only the replacement
    bias-add's broadcast axis differs (last vs 1)."""
    from paddle_tpu.models.resnet import resnet_cifar10

    fluid.unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data("img", shape=[32, 32, 3],
                                dtype="float32")
        logits = resnet_cifar10(img, 10, 8, is_test=True,
                                data_format="NHWC")
    d = str(tmp_path / "m")
    x = np.random.RandomState(0).randn(2, 32, 32, 3).astype("float32")
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(Scope()):
        exe.run(startup)
        (ref,) = exe.run(main, feed={"img": x}, fetch_list=[logits])
        fluid.io.save_inference_model(d, ["img"], [logits], exe,
                                      main_program=main)
    pred = create_paddle_predictor(AnalysisConfig(d))
    ops = [op.type for op in pred.program.global_block().ops]
    assert ops.count("batch_norm") == 0, "NHWC fold did not fire"
    (got,) = pred.run([x])
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)


def test_predictor_params_promoted_to_device_once(tmp_path):
    """The analysis passes compute in numpy: ``fuse_conv_bn`` writes
    the FOLDED weights into the predictor scope as host arrays.  The
    executor must promote those to device arrays ON FIRST RUN and
    write the promotion back — otherwise every dispatch re-transfers
    the whole weight set (measured in round 5: ResNet-50 inference 30x
    slower than its own training step, 2.8 s/batch).  A conv+bn model is essential here: a pure-fc export
    reloads as jax arrays and the test would pass vacuously."""
    main, startup, test_prog, img, label, logits, loss = \
        _build_convbn_model()
    exe = fluid.Executor(fluid.CPUPlace())
    path = str(tmp_path / "m")
    with scope_guard(Scope()):
        exe.run(startup)
        fluid.io.save_inference_model(path, ["img"], [logits], exe,
                                      main_program=test_prog)

    cfg = fluid.inference.AnalysisConfig(model_dir=path)
    pred = fluid.inference.create_paddle_predictor(cfg)
    # the conv+bn fold must have left host numpy in the scope — the
    # precondition that makes this test able to catch a regression
    assert any(isinstance(pred._scope.get(n), np.ndarray)
               and pred._scope.get(n).ndim > 0
               for n in pred._scope.local_var_names())
    feed = {"img": np.random.RandomState(0)
            .randn(2, 3, 8, 8).astype("float32")}
    o1 = pred.run(feed)[0]
    numpy_left = [n for n in pred._scope.local_var_names()
                  if isinstance(pred._scope.get(n), np.ndarray)
                  and pred._scope.get(n).ndim > 0]
    # every weight the run read must now live on device (numpy gone)
    assert not numpy_left, numpy_left
    # and the promotion must not change results across runs
    o2 = pred.run(feed)[0]
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2))


def test_feed_check_survives_inference_model_roundtrip(tmp_path):
    """need_check_feed / feed_hint must round-trip through
    save_inference_model: a loaded serving program feeding a wrong
    inner dim should fail fast with the targeted data-layer ValueError,
    not a jit shape error deep inside the step."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[8], dtype="float32")
        x.feed_hint = "x is the 8-wide feature row"
        out = fluid.layers.fc(x, size=2)
    exe = fluid.Executor(fluid.CPUPlace())
    d = str(tmp_path / "m")
    with scope_guard(Scope()):
        exe.run(startup)
        fluid.io.save_inference_model(d, ["x"], [out], exe,
                                      main_program=main)
        iprog, feeds, fetches = fluid.io.load_inference_model(d, exe)
        v = iprog.global_block().vars[feeds[0]]
        assert v.need_check_feed
        assert v.feed_hint == "x is the 8-wide feature row"
        with pytest.raises(ValueError, match="declares"):
            exe.run(iprog, feed={feeds[0]: np.zeros((4, 5), "float32")},
                    fetch_list=fetches)
