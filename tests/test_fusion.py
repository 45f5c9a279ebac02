"""Cost-guided fusion pass pipeline (ISSUE 5): pattern-match/rewrite
goldens on the example builders, fusion-on vs fusion-off bit-exactness
(train + infer; documented tolerance where fused softmax-xent differs),
bucketed-allreduce deadlock proof, jit-cache-key separation, the kill
switch + fusion_report introspection, and the two new lint checks."""

import copy
import os
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.static_analysis import (FusionConfig, fusion,
                                        prove_deadlock_free,
                                        verify_program)
from paddle_tpu.executor import Scope, scope_guard
from paddle_tpu.transpiler.collective import GradAllReduce


def build_mnist_mlp(act="relu", train=True, lr=1e-3, optimizer="adam",
                    width=24, in_dim=32):
    """fc(relu) x2 -> fc(softmax) -> cross_entropy: exercises the
    bias_act and softmax_xent families."""
    fluid.unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[in_dim],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        h = fluid.layers.fc(input=img, size=width, act=act)
        h = fluid.layers.fc(input=h, size=width, act=act)
        pred = fluid.layers.fc(input=h, size=10, act="softmax")
        loss = fluid.layers.reduce_mean(
            fluid.layers.cross_entropy(input=pred, label=label))
        acc = fluid.layers.accuracy(input=pred, label=label)
        if train:
            opt = (fluid.optimizer.Adam(learning_rate=lr)
                   if optimizer == "adam"
                   else fluid.optimizer.SGD(learning_rate=lr))
            opt.minimize(loss)
    return main, startup, loss, acc, pred


def build_bert_tiny(seq_len=32, train=True, dropout=None):
    """BERT_TINY with the UNfused attention chain so the pipeline (not
    the model builder) performs the rewrite."""
    from paddle_tpu.models import bert

    cfg = copy.copy(bert.BERT_TINY)
    cfg.fuse_attn = False
    cfg.max_seq = max(cfg.max_seq, seq_len)
    if dropout is not None:
        cfg.dropout = dropout
        cfg.attn_dropout = dropout
    fluid.unique_name.switch()
    main, startup, feeds, loss = bert.build_pretrain(
        cfg, seq_len=seq_len, train=train)
    return main, startup, feeds, loss, cfg


def mlp_feed(rng, bs=8):
    return {"img": rng.rand(bs, 32).astype("float32"),
            "label": rng.randint(0, 10, (bs, 1)).astype("int64")}


def run_steps(main, startup, feed, fetch, steps=4):
    exe = fluid.Executor()
    scope = Scope()
    with scope_guard(scope):
        exe.run(startup)
        outs = [np.asarray(exe.run(main, feed=feed, fetch_list=fetch)[0])
                for _ in range(steps)]
    return np.array(outs), scope


def op_types(program):
    return [op.type for op in program.global_block().ops]


# ---------------------------------------------------------------------------
# pattern-match / rewrite goldens
# ---------------------------------------------------------------------------

class TestRewriteGoldens:
    def test_mnist_mlp_families(self):
        main, startup, loss, acc, pred = build_mnist_mlp()
        fused, report = fusion.resolve_fused_program(
            main, targets=[loss.name, acc.name])
        counts = report.counts()
        assert counts.get("bias_act") == 2          # two relu fcs
        assert counts.get("softmax_xent") == 1
        types = op_types(fused)
        assert types.count("fused_bias_act") == 2
        assert types.count("fused_bias_act_grad") == 2
        assert types.count("softmax_with_cross_entropy") == 1
        assert types.count("softmax_with_cross_entropy_grad") == 1
        # each parameter keeps its own update op (XLA fuses each one)
        assert types.count("adam") == op_types(main).count("adam") == 6
        # the rewritten program is strictly smaller and still verifies
        assert len(types) < len(op_types(main))
        verify_program(fused, targets=[loss.name, acc.name])

    def test_bert_tiny_all_families_fire(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_FLASH_MIN_T", "16")
        main, startup, feeds, loss, cfg = build_bert_tiny()
        fused, report = fusion.resolve_fused_program(
            main, targets=[loss.name])
        counts = report.counts()
        assert counts.get("attention") == cfg.layers == 2
        # 2 sublayer closes per layer + the embedding add+LN
        assert counts.get("dropout_add_ln") == 2 * cfg.layers + 1
        assert counts.get("bias_act") == cfg.layers  # gelu ffn1 per layer
        types = op_types(fused)
        assert types.count("fused_multihead_attention") == 2
        assert types.count("fused_multihead_attention_grad") == 2
        assert types.count("fused_dropout_add_ln") == 5
        assert types.count("fused_dropout_add_ln_grad") == 5
        assert "softmax" not in types  # every attention softmax fused
        verify_program(fused, targets=[loss.name])

    def test_bert_train_program_strictly_fewer_ops(self, monkeypatch):
        """Acceptance: with fusion enabled (default) the BERT train step
        lowers to strictly fewer ops than unfused — program-level op
        count, which maps 1:1 onto fewer HLO computations entering XLA."""
        monkeypatch.setenv("PADDLE_TPU_FLASH_MIN_T", "16")
        main, startup, feeds, loss, cfg = build_bert_tiny()
        fused, report = fusion.resolve_fused_program(
            main, targets=[loss.name])
        assert len(op_types(fused)) < len(op_types(main))
        assert report.ops_removed > 0

    @pytest.mark.slow
    def test_bert_base_train_program_strictly_fewer_ops(self, monkeypatch):
        """The BERT-base acceptance criterion at its real scale (IR-only;
        nothing is executed)."""
        from paddle_tpu.models import bert

        monkeypatch.setenv("PADDLE_TPU_FLASH_MIN_T", "128")
        cfg = copy.copy(bert.BERT_BASE)
        cfg.fuse_attn = False
        fluid.unique_name.switch()
        main, _, _, loss = bert.build_pretrain(cfg, seq_len=128,
                                               train=True)
        fused, report = fusion.resolve_fused_program(
            main, targets=[loss.name])
        counts = report.counts()
        assert counts.get("attention") == 12
        assert counts.get("dropout_add_ln") == 25
        assert len(op_types(fused)) < len(op_types(main))

    def test_infer_program_rewrites(self):
        """Inference programs (no grad twins) rewrite forward-only."""
        main, startup, feeds, loss, cfg = build_bert_tiny(train=False)
        fused, report = fusion.resolve_fused_program(
            main, targets=[loss.name])
        counts = report.counts()
        assert counts.get("dropout_add_ln") == 5
        types = op_types(fused)
        assert types.count("fused_dropout_add_ln") == 5
        assert not any(t.endswith("_grad") for t in types)

    def test_fetched_intermediate_is_never_fused_away(self):
        """A fetch of the pre-activation bias-add output must keep the
        unfused chain (the fused op would leave the fetch unproduced)."""
        fluid.unique_name.switch()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[8], dtype="float32")
            h = fluid.layers.fc(input=x, size=4, act="relu")
            loss = fluid.layers.reduce_mean(h)
        # find the elementwise_add output (the intermediate)
        add_out = next(op.outputs["Out"][0]
                       for op in main.global_block().ops
                       if op.type == "elementwise_add")
        fused, report = fusion.resolve_fused_program(
            main, targets=[loss.name, add_out])
        assert report.counts().get("bias_act") is None
        fused2, report2 = fusion.resolve_fused_program(
            main, targets=[loss.name])
        assert report2.counts().get("bias_act") == 1


# ---------------------------------------------------------------------------
# cost gates
# ---------------------------------------------------------------------------

class TestCostGates:
    def test_attention_below_flash_threshold_skips_with_reason(
            self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_FLASH_MIN_T", "512")
        main, startup, feeds, loss, cfg = build_bert_tiny(seq_len=32)
        fused, report = fusion.resolve_fused_program(
            main, targets=[loss.name])
        assert report.counts().get("attention") is None
        skips = [s for s in report.skipped if s.family == "attention"]
        assert len(skips) == cfg.layers
        assert "flash engagement threshold" in skips[0].reason

    def test_attention_dynamic_seq_dim_skips_not_crashes(
            self, monkeypatch):
        """Regression: dynamic Tq with static Tk above the threshold
        passed the cost gate and hit int(None) — must skip instead."""
        import math

        monkeypatch.setenv("PADDLE_TPU_FLASH_MIN_T", "32")
        fluid.unique_name.switch()
        main, startup = fluid.Program(), fluid.Program()
        H, DH = 2, 8
        with fluid.program_guard(main, startup):
            q = fluid.layers.data(name="q", shape=[H, None, DH],
                                  dtype="float32")
            k = fluid.layers.data(name="k", shape=[H, 64, DH],
                                  dtype="float32")
            v = fluid.layers.data(name="v", shape=[H, 64, DH],
                                  dtype="float32")
            scores = fluid.layers.matmul(q, k, transpose_y=True,
                                         alpha=1.0 / math.sqrt(DH))
            probs = fluid.layers.softmax(scores)
            loss = fluid.layers.reduce_mean(
                fluid.layers.matmul(probs, v))
        _, report = fusion.resolve_fused_program(main,
                                                 targets=[loss.name])
        assert report.counts().get("attention") is None
        skips = [s for s in report.skipped if s.family == "attention"]
        assert skips and "dynamic" in skips[0].reason

    def test_skips_not_duplicated_by_applied_rewrites(self, monkeypatch):
        """Regression: the family loop re-scans after every applied
        rewrite, and each scan used to re-record every still-gated
        site — one below-threshold attention next to one fused
        attention listed the same skip twice (quadratic on BERT)."""
        import math

        monkeypatch.setenv("PADDLE_TPU_FLASH_MIN_T", "32")
        fluid.unique_name.switch()
        main, startup = fluid.Program(), fluid.Program()
        H, DH = 2, 8
        with fluid.program_guard(main, startup):
            outs = []
            for T in (64, 16):  # first fuses, second is below threshold
                q = fluid.layers.data(name="q%d" % T, shape=[H, T, DH],
                                      dtype="float32")
                k = fluid.layers.data(name="k%d" % T, shape=[H, T, DH],
                                      dtype="float32")
                v = fluid.layers.data(name="v%d" % T, shape=[H, T, DH],
                                      dtype="float32")
                scores = fluid.layers.matmul(q, k, transpose_y=True,
                                             alpha=1.0 / math.sqrt(DH))
                probs = fluid.layers.softmax(scores)
                outs.append(fluid.layers.reduce_mean(
                    fluid.layers.matmul(probs, v)))
            loss = fluid.layers.elementwise_add(outs[0], outs[1])
        _, report = fusion.resolve_fused_program(main,
                                                 targets=[loss.name])
        assert report.counts().get("attention") == 1
        skips = [s for s in report.skipped if s.family == "attention"]
        assert len(skips) == 1
        assert "flash engagement threshold" in skips[0].reason
        # recorded coordinates must be valid in the reported program
        seen = {(s.family, s.block_idx, s.op_idx) for s in report.skipped}
        assert len(seen) == len(report.skipped)

    def test_attention_rank2_per_row_bias_stays_unfused(self, monkeypatch):
        """Regression: a rank-2 bias trailing-aligns to the (Tq,Tk)
        score dims under the unfused elementwise_add — a per-QUERY-ROW
        bias.  The fused op would reinterpret it per batch, so the
        matcher must refuse it (only [B,1,1,Tk] / [1,Tk] fuse)."""
        import math

        monkeypatch.setenv("PADDLE_TPU_FLASH_MIN_T", "16")
        fluid.unique_name.switch()
        main, startup = fluid.Program(), fluid.Program()
        T, H, DH = 32, 2, 8
        with fluid.program_guard(main, startup):
            q = fluid.layers.data(name="q", shape=[H, T, DH],
                                  dtype="float32")
            k = fluid.layers.data(name="k", shape=[H, T, DH],
                                  dtype="float32")
            v = fluid.layers.data(name="v", shape=[H, T, DH],
                                  dtype="float32")
            rowbias = fluid.layers.data(name="rowbias", shape=[T],
                                        dtype="float32")  # [B,T]: per-row
            scores = fluid.layers.matmul(q, k, transpose_y=True,
                                         alpha=1.0 / math.sqrt(DH))
            scores = fluid.layers.elementwise_add(scores, rowbias)
            probs = fluid.layers.softmax(scores)
            out = fluid.layers.matmul(probs, v)
            loss = fluid.layers.reduce_mean(out)
        _, report = fusion.resolve_fused_program(main,
                                                 targets=[loss.name])
        assert report.counts().get("attention") is None

    def test_differentiable_soft_label_stays_unfused(self):
        """Regression: distillation-style soft label produced by a
        differentiable teacher path.  The fused op emits Logits@GRAD
        only, so fusing would leave the teacher's softmax_grad reading
        a never-produced Label@GRAD — the matcher must refuse."""
        fluid.unique_name.switch()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[8], dtype="float32")
            teacher = fluid.layers.softmax(
                fluid.layers.fc(input=x, size=4, act=None))
            student = fluid.layers.softmax(
                fluid.layers.fc(input=x, size=4, act=None))
            loss = fluid.layers.reduce_mean(fluid.layers.cross_entropy(
                student, teacher, soft_label=True))
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        fused, report = fusion.resolve_fused_program(
            main, targets=[loss.name])
        assert report.counts().get("softmax_xent") is None
        skips = [s for s in report.skipped if s.family == "softmax_xent"]
        assert skips and "differentiable" in skips[0].reason
        # the program must still run with fusion on
        rng = np.random.RandomState(3)
        feed = {"x": rng.rand(4, 8).astype("float32")}
        run_steps(main, startup, feed, [loss.name], steps=1)

    def test_ops_removed_matches_actual_program_shrink(self):
        main, startup, loss, acc, pred = build_mnist_mlp()
        n_before = len(main.global_block().ops)
        fused, report = fusion.resolve_fused_program(
            main, targets=[loss.name])
        n_after = len(fused.global_block().ops)
        assert report.ops_removed == n_before - n_after > 0

    def test_rewrite_records_coordinates_and_deltas(self):
        main, startup, loss, acc, pred = build_mnist_mlp()
        fused, report = fusion.resolve_fused_program(
            main, targets=[loss.name])
        for r in report.applied:
            assert r.block_idx == 0
            assert len(r.op_idxs) >= 2
            assert r.predicted  # every rewrite carries a predicted delta
        d = report.to_dict()
        assert d["counts"] == report.counts()


# ---------------------------------------------------------------------------
# bit-exactness / documented tolerance
# ---------------------------------------------------------------------------

class TestNumerics:
    def test_bias_act_train_bit_exact(self, monkeypatch):
        """The family documented bit-exact (bias_act composite):
        identical losses and identical final params.  The model avoids
        the softmax-xent family so the whole program is in the bit-exact
        class."""
        def build():
            fluid.unique_name.switch()
            main, startup = fluid.Program(), fluid.Program()
            with fluid.program_guard(main, startup):
                x = fluid.layers.data(name="img", shape=[32],
                                      dtype="float32")
                y = fluid.layers.data(name="label", shape=[1],
                                      dtype="float32")
                h = fluid.layers.fc(input=x, size=16, act="relu")
                h = fluid.layers.fc(input=h, size=16, act="tanh")
                out = fluid.layers.fc(input=h, size=1)
                loss = fluid.layers.reduce_mean(
                    fluid.layers.square(
                        fluid.layers.elementwise_sub(out, y)))
                fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
            return main, startup, loss
        rng = np.random.RandomState(3)
        feed = {"img": rng.rand(8, 32).astype("float32"),
                "label": rng.rand(8, 1).astype("float32")}
        monkeypatch.setenv("PADDLE_TPU_FUSION", "0")
        m0, s0, loss0 = build()
        off, sc_off = run_steps(m0, s0, feed, [loss0.name])
        monkeypatch.setenv("PADDLE_TPU_FUSION", "1")
        m1, s1, loss1 = build()
        # prove the rewrites actually fired on the fusion-on arm
        rep = fusion.resolve_fused_program(m1, targets=[loss1.name])[1]
        assert rep.counts().get("bias_act") == 2
        on, sc_on = run_steps(m1, s1, feed, [loss1.name])
        np.testing.assert_array_equal(off, on)
        w_off = np.asarray(sc_off.get("fc_0.w_0"))
        w_on = np.asarray(sc_on.get("fc_0.w_0"))
        np.testing.assert_array_equal(w_off, w_on)

    def test_softmax_xent_train_documented_tolerance(self, monkeypatch):
        """The softmax-xent family is NOT bit-exact (logsumexp form vs
        the eps-guarded log(softmax)+pick) — documented tolerance 1e-5
        relative over a few steps."""
        rng = np.random.RandomState(0)
        feed = mlp_feed(rng)
        monkeypatch.setenv("PADDLE_TPU_FUSION", "0")
        m, s, loss, acc, _ = build_mnist_mlp()
        off, _ = run_steps(m, s, feed, [loss.name])
        monkeypatch.setenv("PADDLE_TPU_FUSION", "1")
        m, s, loss, acc, _ = build_mnist_mlp()
        on, _ = run_steps(m, s, feed, [loss.name])
        np.testing.assert_allclose(on, off, rtol=1e-5)
        assert on[-1] < on[0]  # still trains

    def test_bert_infer_dropout0_bit_exact_ln_family(self, monkeypatch):
        """Rate-0 fused_dropout_add_ln is bit-exact in f32: the bert
        eval program (all dropout off) produces the identical loss with
        fusion on and off."""
        monkeypatch.setenv("PADDLE_TPU_FLASH_MIN_T", "512")
        rng = np.random.RandomState(1)
        from paddle_tpu.models import bert

        main, startup, feeds, loss, cfg = build_bert_tiny(train=False)
        batch = bert.make_fake_batch(4, 32, cfg, rng)
        monkeypatch.setenv("PADDLE_TPU_FUSION", "0")
        off, _ = run_steps(main, startup, batch, [loss.name], steps=2)
        monkeypatch.setenv("PADDLE_TPU_FUSION", "1")
        on, _ = run_steps(main, startup, batch, [loss.name], steps=2)
        np.testing.assert_array_equal(off, on)

    def test_bert_train_with_attention_fusion_converges(self, monkeypatch):
        """Attention + LN fusion in train mode: dropout mask streams
        differ (documented), so assert convergence parity, not
        bit-exactness."""
        monkeypatch.setenv("PADDLE_TPU_FLASH_MIN_T", "16")
        rng = np.random.RandomState(2)
        from paddle_tpu.models import bert

        main, startup, feeds, loss, cfg = build_bert_tiny()
        batch = bert.make_fake_batch(4, 32, cfg, rng)
        monkeypatch.setenv("PADDLE_TPU_FUSION", "1")
        on, _ = run_steps(main, startup, batch, [loss.name], steps=4)
        assert np.isfinite(on).all()
        assert on[-1] < on[0]


# ---------------------------------------------------------------------------
# bucketed allreduce
# ---------------------------------------------------------------------------

def build_dp_mlp(rank=0, nranks=2, lr=0.1):
    fluid.unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[16], dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        h = fluid.layers.fc(input=x, size=32, act="relu")
        h = fluid.layers.fc(input=h, size=32, act="relu")
        pred = fluid.layers.fc(input=h, size=4, act="softmax")
        loss = fluid.layers.reduce_mean(
            fluid.layers.cross_entropy(input=pred, label=label))
        fluid.optimizer.SGD(learning_rate=lr).minimize(loss)
    GradAllReduce().transpile(program=main, startup_program=startup,
                              rank=rank, nranks=nranks)
    main._num_trainers = nranks
    return main, startup, loss


class TestBucketedAllreduce:
    def test_coalesces_into_buckets(self):
        main, startup, loss = build_dp_mlp()
        n_before = op_types(main).count("c_allreduce_sum")
        assert n_before == 6
        fused, report = fusion.resolve_fused_program(
            main, targets=[loss.name])
        types = op_types(fused)
        assert types.count("c_fused_allreduce_sum") == 1
        assert types.count("c_allreduce_sum") == 0
        (rw,) = [r for r in report.applied if r.family == "allreduce"]
        assert rw.predicted["collectives_removed"] == 5

    def test_bucket_cap_splits(self, monkeypatch):
        # grads total ~6.9KB; a 4KB cap must split into >=2 buckets
        monkeypatch.setenv("PADDLE_TPU_ALLREDUCE_BUCKET_MB", "0.004")
        main, startup, loss = build_dp_mlp()
        fused, report = fusion.resolve_fused_program(
            main, targets=[loss.name])
        types = op_types(fused)
        # a bucket surfaces as the fused op, or as a start/wait pair
        # once the overlap scheduler (PR 16) hoists it
        n_buckets = (types.count("c_fused_allreduce_sum")
                     + types.count("c_allreduce_start"))
        assert n_buckets >= 2

    def test_sub_block_closure_read_blocks_coalescing(self):
        """A conditional body reading a grad by closure (no input slot)
        between its allreduce and the flush site would see the
        un-reduced local value — that member must stay unfused."""
        main, startup, loss = build_dp_mlp()
        block = main.global_block()
        idxs = [i for i, op in enumerate(block.ops)
                if op.type == "c_allreduce_sum"]
        g = block.ops[idxs[0]].inputs["X"][0]
        sub = main._create_block()
        sub.create_var(name="peek", shape=[1], dtype="float32")
        sub.append_op(type="scale", inputs={"X": [g]},
                      outputs={"Out": ["peek"]}, attrs={"scale": 1.0})
        from paddle_tpu.framework import Operator
        cf = Operator(block, "conditional_block", inputs={}, outputs={},
                      attrs={"sub_block": sub.idx})
        block.ops.insert(idxs[0] + 1, cf)
        fused, report = fusion.resolve_fused_program(
            main, targets=[loss.name])
        skips = [s for s in report.skipped if s.family == "allreduce"]
        assert any(g in s.reason for s in skips), [s.reason for s in skips]
        types = op_types(fused)
        assert types.count("c_allreduce_sum") == 1  # the guarded member
        assert types.count("c_fused_allreduce_sum") == 1  # the rest

    def test_schedule_passes_deadlock_proof(self):
        w = []
        for rank in range(2):
            main, _, loss = build_dp_mlp(rank=rank)
            fused, _ = fusion.resolve_fused_program(
                main, targets=[loss.name])
            w.append(fused)
        schedules, diags = prove_deadlock_free(w, nranks=2)
        assert diags == []
        evs = schedules[0].get(0, [])
        assert [e.op_type for e in evs] == ["c_fused_allreduce_sum"]
        # ICI payload is the SUM of the coalesced members
        assert evs[0].numel == 16 * 32 + 32 + 32 * 32 + 32 + 32 * 4 + 4

    def test_gspmd_identity_bit_exact(self, monkeypatch):
        """Under the GSPMD (no shard_map) path the bucketed collective
        is an identity like the scalar one: training is bit-exact with
        the unfused program."""
        rng = np.random.RandomState(5)
        feed = {"x": rng.rand(8, 16).astype("float32"),
                "label": rng.randint(0, 4, (8, 1)).astype("int64")}
        monkeypatch.setenv("PADDLE_TPU_FUSION", "0")
        m, s, loss = build_dp_mlp()
        off, _ = run_steps(m, s, feed, [loss.name])
        monkeypatch.setenv("PADDLE_TPU_FUSION", "1")
        m, s, loss = build_dp_mlp()
        rep = fusion.resolve_fused_program(m, targets=[loss.name])[1]
        assert rep.counts().get("allreduce") == 1
        on, _ = run_steps(m, s, feed, [loss.name])
        # softmax_xent also fires on both arms? no: fusion-off arm is
        # fully unfused; compare within the documented tolerance
        np.testing.assert_allclose(on, off, rtol=1e-5)


# ---------------------------------------------------------------------------
# kill switch, report, cache-key separation
# ---------------------------------------------------------------------------

class TestIntrospectionAndCaching:
    def test_kill_switch_disables_everything(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_FUSION", "0")
        main, startup, loss, acc, pred = build_mnist_mlp()
        fused, report = fusion.resolve_fused_program(
            main, targets=[loss.name])
        assert fused is main
        assert report.applied == []
        assert not report.config.enabled

    def test_compiled_program_fusion_report(self):
        main, startup, loss, acc, pred = build_mnist_mlp()
        cp = fluid.CompiledProgram(main)
        report = cp.fusion_report()
        assert report.counts().get("softmax_xent") == 1
        assert "softmax_with_cross_entropy" in report.format()

    def test_build_strategy_flags_gate_families(self):
        main, startup, loss, acc, pred = build_mnist_mlp()
        bs = fluid.BuildStrategy()
        bs.fuse_elewise_add_act_ops = False
        config = FusionConfig.from_build_strategy(bs)
        fused, report = fusion.resolve_fused_program(
            main, config=config, targets=[loss.name])
        counts = report.counts()
        assert counts.get("bias_act") is None
        assert counts.get("softmax_xent") == 1  # its own flag, still on

    def test_fuse_all_optimizer_ops_is_accepted_and_inert(self):
        """Fluid's own flag stays settable and gates nothing: XLA fuses
        each parameter's update, so the op list is the same either way."""
        main, startup, loss, acc, pred = build_mnist_mlp()
        resolved = []
        for flag in (True, False):
            bs = fluid.BuildStrategy()
            bs.fuse_all_optimizer_ops = flag
            fused, _ = fusion.resolve_fused_program(
                main, config=FusionConfig.from_build_strategy(bs),
                targets=[loss.name])
            resolved.append(op_types(fused))
        assert resolved[0] == resolved[1]
        assert resolved[0].count("adam") == 6

    def test_plain_compiled_program_honors_disabled_flags(self):
        """Regression: with a BuildStrategy that disables a family, the
        plain (non-DP) CompiledProgram path must NOT fall back to the
        default config in Executor.run — even when the strategy's own
        resolve applies zero rewrites."""
        rng = np.random.RandomState(0)
        feed = mlp_feed(rng)
        fluid.unique_name.switch()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="img", shape=[32],
                                  dtype="float32")
            h = fluid.layers.fc(input=x, size=8, act="relu")
            loss = fluid.layers.reduce_mean(h)
        bs = fluid.BuildStrategy()
        bs.fuse_elewise_add_act_ops = False  # the ONLY matching family
        cp = fluid.CompiledProgram(main, build_strategy=bs)
        exe = fluid.Executor()
        with scope_guard(Scope()):
            exe.run(startup)
            exe.run(cp, feed={"img": feed["img"]},
                    fetch_list=[loss.name])
            assert cp.fusion_report().counts() == {}
            # the executor must have compiled the UNfused program: no
            # fusion signature in any cache key
            assert all(k[-1] is None for k in exe._cache)

    def test_jit_cache_key_separates_fusion_configs(self, monkeypatch):
        """The same source program under fusion on/off compiles into
        DIFFERENT executor cache entries (fusion config is part of the
        compilation identity)."""
        rng = np.random.RandomState(0)
        feed = mlp_feed(rng)
        main, startup, loss, acc, pred = build_mnist_mlp()
        exe = fluid.Executor()
        with scope_guard(Scope()):
            exe.run(startup)
            monkeypatch.setenv("PADDLE_TPU_FUSION", "1")
            exe.run(main, feed=feed, fetch_list=[loss.name])
            n_on = len(exe._cache)
            monkeypatch.setenv("PADDLE_TPU_FUSION", "0")
            exe.run(main, feed=feed, fetch_list=[loss.name])
            assert len(exe._cache) > n_on
            keys = list(exe._cache)
            sigs = {k[-1] for k in keys if len(k) >= 8}
            assert None in sigs and len(sigs) >= 2

    def test_resolution_is_cached(self):
        main, startup, loss, acc, pred = build_mnist_mlp()
        f1, r1 = fusion.resolve_fused_program(main, targets=[loss.name])
        f2, r2 = fusion.resolve_fused_program(main, targets=[loss.name])
        assert f1 is f2 and r1 is r2

    def test_resolve_cache_is_bounded(self):
        """A serving loop fetching distinct var subsets must not
        accumulate unbounded program clones on the source program."""
        main, startup, loss, acc, pred = build_mnist_mlp()
        names = [loss.name, acc.name, pred.name]
        for i in range(fusion._FUSION_CACHE_CAP + 8):
            fusion.resolve_fused_program(
                main, targets=names[:1 + i % 3] + ["dummy_%d" % i])
        assert len(main.__dict__["_fusion_cache"]) \
            <= fusion._FUSION_CACHE_CAP

    def test_scan_is_side_effect_free(self):
        main, startup, loss, acc, pred = build_mnist_mlp()
        before = op_types(main)
        report = fusion.scan_fusible_patterns(main, targets=[loss.name])
        assert op_types(main) == before
        assert report.counts().get("softmax_xent") == 1


# ---------------------------------------------------------------------------
# lint checks
# ---------------------------------------------------------------------------

class TestLintChecks:
    def test_fused_op_missing_grad_fires(self):
        from paddle_tpu.ops.registry import register_op

        register_op("fused_test_nograd", inputs=["X"], outputs=["Out"],
                    no_grad=True)(lambda ctx, attrs, X: X)
        fluid.unique_name.switch()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            w = fluid.layers.create_parameter([4], "float32", name="w")
            h = fluid.layers.elementwise_mul(x, w)
            block = main.global_block()
            out = block.create_var(name="ftng_out", shape=[-1, 4],
                                   dtype="float32")
            block.append_op(type="fused_test_nograd",
                            inputs={"X": [h]}, outputs={"Out": [out]})
            # the loss DEMANDS a gradient through the fused op (the
            # parallel h path keeps minimize able to produce w@GRAD)
            loss = fluid.layers.reduce_mean(
                fluid.layers.elementwise_add(out, h))
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        diags = verify_program(main, targets=[loss.name])
        hits = [d for d in diags if d.check == "fused-op-missing-grad"]
        assert hits, [str(d) for d in diags]
        from paddle_tpu.static_analysis import Severity

        assert hits[0].severity == Severity.ERROR

    def test_metrics_only_fused_op_does_not_fire_missing_grad(self):
        """A no_grad fused op on a fetch/metrics-only branch demands no
        gradient — training is correct, so no ERROR."""
        from paddle_tpu.ops.registry import register_op

        register_op("fused_test_nograd2", inputs=["X"], outputs=["Out"],
                    no_grad=True)(lambda ctx, attrs, X: X)
        fluid.unique_name.switch()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            w = fluid.layers.create_parameter([4], "float32", name="w2")
            h = fluid.layers.elementwise_mul(x, w)
            block = main.global_block()
            metric = block.create_var(name="ftng2_out", shape=[-1, 4],
                                      dtype="float32")
            block.append_op(type="fused_test_nograd2",
                            inputs={"X": [h]}, outputs={"Out": [metric]})
            loss = fluid.layers.reduce_mean(h)
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        diags = verify_program(main, targets=[loss.name, metric.name])
        hits = [d for d in diags if d.check == "fused-op-missing-grad"]
        assert not hits, [str(d) for d in hits]

    def test_pipeline_fused_ops_do_not_trip_missing_grad(self):
        main, startup, loss, acc, pred = build_mnist_mlp()
        fused, _ = fusion.resolve_fused_program(main, targets=[loss.name])
        diags = verify_program(fused, targets=[loss.name])
        assert not [d for d in diags
                    if d.check == "fused-op-missing-grad"]

    def test_fusible_pattern_not_fused_advisory(self, monkeypatch):
        """A matched-but-cost-gated pattern surfaces as an INFO
        advisory naming the cost-model reason."""
        monkeypatch.setenv("PADDLE_TPU_FLASH_MIN_T", "512")
        main, startup, feeds, loss, cfg = build_bert_tiny(seq_len=32)
        diags = verify_program(main, targets=[loss.name])
        hits = [d for d in diags
                if d.check == "fusible-pattern-not-fused"]
        assert hits
        assert any("flash engagement threshold" in d.message
                   for d in hits)

    def test_kill_switch_surfaces_disabled_patterns(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_FUSION", "0")
        main, startup, loss, acc, pred = build_mnist_mlp()
        diags = verify_program(main, targets=[loss.name])
        hits = [d for d in diags
                if d.check == "fusible-pattern-not-fused"
                and "PADDLE_TPU_FUSION=0" in d.message]
        assert hits


# ---------------------------------------------------------------------------
# pallas fallback plumbing (satellite)
# ---------------------------------------------------------------------------

class TestPallasFallback:
    def test_gating_predicate_modes(self, monkeypatch):
        """``off`` wins over everything and an unknown mode is an error,
        not a silent ``auto``."""
        from paddle_tpu.ops.pallas import use_pallas

        monkeypatch.setenv("PADDLE_TPU_PALLAS", "off")
        assert use_pallas() == (False, False)
        monkeypatch.setenv("PADDLE_TPU_PALLAS", "on")
        with pytest.raises(ValueError, match="PADDLE_TPU_PALLAS"):
            use_pallas()

    def test_rewritten_attention_runs_on_cpu_without_pallas(
            self, monkeypatch):
        """The fused attention op reached by the REWRITE (not the model
        builder) must execute on CPU via the XLA composite — the tier-1
        guarantee that the fusion plumbing is exercised without Pallas."""
        monkeypatch.setenv("PADDLE_TPU_FLASH_MIN_T", "16")
        monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
        rng = np.random.RandomState(7)
        from paddle_tpu.models import bert

        main, startup, feeds, loss, cfg = build_bert_tiny(dropout=0.0)
        fused, report = fusion.resolve_fused_program(
            main, targets=[loss.name])
        assert report.counts().get("attention") == 2
        batch = bert.make_fake_batch(2, 32, cfg, rng)
        out, _ = run_steps(main, startup, batch, [loss.name], steps=2)
        assert np.isfinite(out).all()


# ---------------------------------------------------------------------------
# conv + batch_norm + act family (ISSUE 6)
# ---------------------------------------------------------------------------

def build_conv_bn(act="relu", train=True, width=8, hw=16):
    """conv(bias-free) -> batch_norm(act) x2 -> pool -> fc: two
    conv_bn_act sites (one with act, one without)."""
    fluid.unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[width, hw, hw],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        c = fluid.layers.conv2d(img, num_filters=width, filter_size=3,
                                padding=1, bias_attr=False)
        h = fluid.layers.batch_norm(c, act=act)
        c2 = fluid.layers.conv2d(h, num_filters=width, filter_size=3,
                                 padding=1, bias_attr=False)
        h2 = fluid.layers.batch_norm(c2, act=None)
        pool = fluid.layers.pool2d(h2, pool_size=hw, pool_type="avg")
        pred = fluid.layers.fc(pool, size=10, act="softmax")
        loss = fluid.layers.reduce_mean(
            fluid.layers.cross_entropy(input=pred, label=label))
        if train:
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def conv_feed(rng, bs=4, width=8, hw=16):
    return {"img": rng.randn(bs, width, hw, hw).astype("float32"),
            "label": rng.randint(0, 10, (bs, 1)).astype("int64")}


class TestConvBnActFamily:
    def test_rewrite_golden_with_and_without_act(self):
        main, startup, loss = build_conv_bn()
        fused, report = fusion.resolve_fused_program(
            main, targets=[loss.name])
        assert report.counts().get("conv_bn_act") == 2
        types = op_types(fused)
        assert types.count("fused_conv_bn_act") == 2
        assert types.count("fused_conv_bn_act_grad") == 2
        assert types.count("batch_norm") == 0
        assert types.count("conv2d") == 0
        # one site carries the act, the other is the bare conv+bn close
        acts = [op.attrs.get("act_type")
                for op in fused.global_block().ops
                if op.type == "fused_conv_bn_act"]
        assert sorted(acts) == ["", "relu"]
        verify_program(fused, targets=[loss.name])

    def test_resnet_builder_fuses_every_conv_bn_site(self):
        from paddle_tpu.models import resnet

        fluid.unique_name.switch()
        main, startup, feeds, loss, acc = resnet.build(dataset="cifar10")
        fused, report = fusion.resolve_fused_program(
            main, targets=[loss.name])
        # depth-20 cifar resnet: 1 stem + 18 block convs + 2 shortcut
        # projections, every one behind a batch_norm
        assert report.counts().get("conv_bn_act") == 21
        assert op_types(fused).count("batch_norm") == 0

    def test_train_bit_exact_family_isolated(self, monkeypatch):
        """Fusion-on vs conv-family-gated-off over real train steps is
        BIT-EXACT on the XLA composite path (the acceptance bar)."""
        rng = np.random.RandomState(0)
        feed = conv_feed(rng)

        def arm(gate):
            if gate is not None:
                monkeypatch.setenv("PADDLE_TPU_CONV_BN_MIN_BYTES", gate)
            else:
                monkeypatch.delenv("PADDLE_TPU_CONV_BN_MIN_BYTES",
                                   raising=False)
            main, startup, loss = build_conv_bn()
            out, _ = run_steps(main, startup, feed, [loss.name], steps=4)
            return out

        on = arm(None)
        off = arm("1000000000000")
        assert np.array_equal(on, off)

    def test_infer_program_rewrites_forward_only(self):
        main, startup, loss = build_conv_bn(train=False)
        fused, report = fusion.resolve_fused_program(
            main, targets=[loss.name])
        assert report.counts().get("conv_bn_act") == 2
        assert not any(t.endswith("_grad") for t in op_types(fused))

    def test_fetched_conv_out_is_never_fused_away(self):
        main, startup, loss = build_conv_bn()
        conv_out = next(op.outputs["Output"][0]
                        for op in main.global_block().ops
                        if op.type == "conv2d")
        fused, report = fusion.resolve_fused_program(
            main, targets=[loss.name, conv_out])
        assert report.counts().get("conv_bn_act", 0) <= 1  # site 1 kept
        assert conv_out in {n for op in fused.global_block().ops
                            for n in op.output_arg_names}

    def test_running_stats_update_identically(self, monkeypatch):
        """MeanOut/VarianceOut ride the fused op: after N steps the
        running stats in scope match the unfused run bit-for-bit."""
        rng = np.random.RandomState(1)
        feed = conv_feed(rng)

        def arm(gate):
            if gate is not None:
                monkeypatch.setenv("PADDLE_TPU_CONV_BN_MIN_BYTES", gate)
            else:
                monkeypatch.delenv("PADDLE_TPU_CONV_BN_MIN_BYTES",
                                   raising=False)
            main, startup, loss = build_conv_bn()
            mean_name = next(
                op.outputs["MeanOut"][0]
                for op in main.global_block().ops
                if op.type == "batch_norm")
            exe = fluid.Executor()
            scope = Scope()
            with scope_guard(scope):
                exe.run(startup)
                for _ in range(3):
                    exe.run(main, feed=feed, fetch_list=[loss.name])
                return np.asarray(scope.get(mean_name))

        on = arm(None)
        off = arm("1000000000000")
        assert np.array_equal(on, off)

    def test_cost_gate_skip_names_uncalibrated_autotune(
            self, monkeypatch, tmp_path):
        """Satellite: the advisory reason carries the autotune state —
        an empty cache reads 'uncalibrated' with the signature to sweep."""
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_CACHE",
                           str(tmp_path / "at.json"))
        monkeypatch.setenv("PADDLE_TPU_CONV_BN_MIN_BYTES", "1000000000000")
        from paddle_tpu import autotune
        autotune.reset()
        main, startup, loss = build_conv_bn()
        fused, report = fusion.resolve_fused_program(
            main, targets=[loss.name])
        assert report.counts().get("conv_bn_act") is None
        skips = [s for s in report.skipped if s.family == "conv_bn_act"]
        assert skips
        assert "uncalibrated" in skips[0].reason
        assert "conv_bn_act|" in skips[0].reason  # the signature to sweep
        autotune.reset()

    def test_lint_advisory_covers_new_families(self, monkeypatch,
                                               tmp_path):
        """Satellite: fusible-pattern-not-fused surfaces the gated-out
        conv+bn+act sites with the autotune cost-gate reason."""
        from paddle_tpu import autotune

        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_CACHE",
                           str(tmp_path / "at.json"))
        monkeypatch.setenv("PADDLE_TPU_CONV_BN_MIN_BYTES", "1000000000000")
        autotune.reset()
        main, startup, loss = build_conv_bn()
        diags = verify_program(main, targets=[loss.name])
        hits = [d for d in diags
                if d.check == "fusible-pattern-not-fused"
                and "conv_bn_act" in d.message]
        assert hits
        assert any("uncalibrated" in d.message for d in hits)
        autotune.reset()

    def test_calibration_flips_the_gate(self, monkeypatch, tmp_path):
        """The measure-and-learn loop closed: a recorded calibration
        factor scales the predicted delta past the gate."""
        from paddle_tpu import autotune

        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_CACHE",
                           str(tmp_path / "at.json"))
        autotune.reset()
        main, startup, loss = build_conv_bn()
        # gate sits just above the un-calibrated predicted saving
        conv_out_bytes = 8 * 16 * 16 * 4  # batch=1 resolution
        monkeypatch.setenv("PADDLE_TPU_CONV_BN_MIN_BYTES",
                           str(conv_out_bytes * 2))
        _, rep_uncal = fusion.resolve_fused_program(
            main, targets=[loss.name])
        assert rep_uncal.counts().get("conv_bn_act") is None
        # a silicon sweep measured 4x the predicted gain -> gate opens
        ov = next(op for op in main.global_block().ops
                  if op.type == "conv2d").outputs["Output"][0]
        shape = tuple(main.global_block()._find_var_recursive(ov).shape)
        for act in ("relu", "identity"):
            autotune.record(
                autotune.sweep_signature(
                    "conv_bn_act", {"shape": shape, "dtype": "float32",
                                    "act": act}),
                {"params": {}, "calibration": 4.0})
        _, rep_cal = fusion.resolve_fused_program(main,
                                                  targets=[loss.name])
        assert rep_cal.counts().get("conv_bn_act") == 2
        autotune.reset()

    def test_pallas_epilogue_interpret_close_to_xla(self, monkeypatch):
        """PADDLE_TPU_PALLAS=interpret routes the NHWC lane-aligned
        epilogue through the kernel; tolerance documented ~1e-6."""
        monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
        import jax.numpy as jnp
        from paddle_tpu.ops.registry import (LoweringContext, call_op,
                                             get_op_def)

        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(2, 8, 8, 128).astype("float32"))
        w = jnp.asarray(rng.randn(128, 128, 1, 1).astype("float32") * .1)
        g = jnp.asarray(rng.rand(128).astype("float32") + 0.5)
        b = jnp.asarray(rng.randn(128).astype("float32"))
        attrs = {"strides": [1, 1], "paddings": [0, 0],
                 "dilations": [1, 1], "groups": 1,
                 "data_format": "NHWC", "data_layout": "NHWC",
                 "epsilon": 1e-5, "momentum": 0.9, "is_test": False,
                 "act_type": "relu"}
        ins = {"Input": [x], "Filter": [w], "Scale": [g], "Bias": [b],
               "Mean": [jnp.zeros(128)], "Variance": [jnp.ones(128)]}
        fused = get_op_def("fused_conv_bn_act")
        pal = call_op(fused, LoweringContext(), ins, attrs, 1)["Out"][0]
        monkeypatch.setenv("PADDLE_TPU_PALLAS", "off")
        xla = call_op(fused, LoweringContext(), ins, attrs, 1)["Out"][0]
        np.testing.assert_allclose(np.asarray(pal), np.asarray(xla),
                                   rtol=2e-5, atol=2e-5)


class TestConvBnActAmp:
    def test_amp_cast_sandwich_is_absorbed(self):
        """The bf16 AMP rewrite cast-sandwiches BN (conv -> cast f32 ->
        bn -> cast bf16 -> act); the matcher absorbs the pair — every
        resnet conv+bn site still fuses under AMP (the bench config)."""
        from paddle_tpu.models import resnet

        fluid.unique_name.switch()
        main, startup, feeds, loss, acc = resnet.build(
            dataset="cifar10", amp=True)
        fused, report = fusion.resolve_fused_program(
            main, targets=[loss.name])
        assert report.counts().get("conv_bn_act") == 21
        assert op_types(fused).count("batch_norm") == 0
        # the rewrite note documents the AMP tolerance exception
        conv_rewrites = [r for r in report.applied
                         if r.family == "conv_bn_act"]
        assert any("AMP cast sandwich" in r.note for r in conv_rewrites)

    def test_amp_train_within_documented_tolerance(self, monkeypatch):
        """AMP A/B: losses track within float-noise tolerance.  NOT
        bit-exact by design — absorbing the cast sandwich lets XLA
        reassociate the BN scale/bias gradient reductions (f32-stored
        grads show ~1e-4 relative noise; bf16-stored conv grads round
        identically) — the documented exception, mirroring the
        softmax_xent ~1e-6 precedent."""
        import jax.numpy as jnp
        from paddle_tpu.models import resnet

        rng = np.random.RandomState(0)
        feed = {"img": jnp.asarray(
                    rng.randn(4, 3, 32, 32).astype("float32")),
                "label": jnp.asarray(
                    rng.randint(0, 10, (4, 1)).astype("int64"))}

        def arm(gate):
            if gate is not None:
                monkeypatch.setenv("PADDLE_TPU_CONV_BN_MIN_BYTES", gate)
            else:
                monkeypatch.delenv("PADDLE_TPU_CONV_BN_MIN_BYTES",
                                   raising=False)
            fluid.unique_name.switch()
            main, startup, feeds, loss, acc = resnet.build(
                dataset="cifar10", amp=True)
            exe = fluid.Executor()
            with scope_guard(Scope()):
                exe.run(startup)
                return [float(np.asarray(exe.run(
                    main, feed=feed, fetch_list=[loss])[0]).reshape(()))
                    for _ in range(3)]

        on = arm(None)
        off = arm("1000000000000")
        assert np.isfinite(on).all() and np.isfinite(off).all()
        np.testing.assert_allclose(on, off, rtol=2e-2, atol=1e-2)


# ---------------------------------------------------------------------------
# the hand-kept lists agree: flags, rows, signature
# ---------------------------------------------------------------------------

def _main_and_loss(build, loss_at=2, **kwargs):
    built = build(**kwargs)
    return built[0], built[loss_at]


_FLAG_PROGRAMS = {
    "fuse_attention": lambda: _main_and_loss(build_bert_tiny, 3,
                                             seq_len=512),
    "fuse_elewise": lambda: _main_and_loss(build_mnist_mlp),
    "fuse_softmax_xent": lambda: _main_and_loss(build_mnist_mlp),
    "fuse_conv_bn_act": lambda: _main_and_loss(build_conv_bn),
    "fuse_allreduce": lambda: _main_and_loss(build_dp_mlp),
}


@pytest.mark.parametrize(
    "flag", [f for f in FusionConfig.__slots__ if f != "enabled"])
def test_each_family_flag_is_in_the_signature_and_gates_its_rows(flag):
    """``FusionConfig``'s flags, ``_FAMILIES``' rows and ``signature()``
    are three lists kept by hand; a flag missing from the signature is a
    stale fused clone (fixed three times, ROADMAP Queue 3).  Turning one
    flag off changes the signature, takes out exactly that flag's
    families and leaves the others' counts alone."""
    rows = [family for family, f, _ in fusion._FAMILIES if f == flag]
    assert rows, "no family reads %s" % flag
    main, loss = _FLAG_PROGRAMS[flag]()
    off = FusionConfig()
    setattr(off, flag, False)
    assert off.signature(main) != FusionConfig().signature(main)
    on_counts = fusion.resolve_fused_program(
        main, targets=[loss.name])[1].counts()
    off_counts = fusion.resolve_fused_program(
        main, config=off, targets=[loss.name])[1].counts()
    assert any(on_counts.get(family) for family in rows)
    assert off_counts == {family: n for family, n in on_counts.items()
                          if family not in rows}


@pytest.mark.parametrize("optimizer", ["sgd", "adam"])
def test_each_parameter_keeps_its_own_update_op(optimizer):
    """No rewrite concatenates the parameters: the resolved program has
    one update op a parameter, the op XLA fuses on its own."""
    main, _, loss, _, _ = build_mnist_mlp(optimizer=optimizer)
    fused, _ = fusion.resolve_fused_program(main, targets=[loss.name])
    params = main.global_block().all_parameters()
    assert op_types(fused).count(optimizer) == len(params) == 6


# ---------------------------------------------------------------------------
# no dead forks: every family is reached by a default configuration
# ---------------------------------------------------------------------------

def _cell_programs():
    """The benchmark cells' training programs from their own builders and
    files, cut in depth alone (one layer; ResNet's 50 is a table row)."""
    from chipbench import manifest as mf

    manifest = mf.load_manifest()
    for entry in manifest["workloads"]:
        cell = mf.load_cell(entry["name"], manifest)
        config = dict(cell["config"])
        if "num_hidden_layers" in config:
            # kanana's layer 0 is dense: two layers reach an expert layer
            config["num_hidden_layers"] = \
                1 + config.get("first_k_dense_replace", 0)
        builder = mf.load_by_name("builders", config["builder"])
        _, _, loss, main = builder.build(
            config, cell["workload"]["program"], cell["traffic"], 0)
        yield main, [loss.name]


def _example_programs():
    examples = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "examples")
    if examples not in sys.path:
        sys.path.insert(0, examples)
    import bert_pretrain
    import mnist_train

    main, _, _, loss, acc = mnist_train.build_program()
    yield main, [loss.name, acc.name]
    main, _, _, loss = bert_pretrain.build_program(tiny=True, seq_len=32)
    yield main, [loss.name]


def _this_files_programs():
    main, _, loss, acc, _ = build_mnist_mlp()
    yield main, [loss.name, acc.name]
    # attention at the flash threshold's default, the smallest T it admits
    main, _, _, loss, _ = build_bert_tiny(seq_len=512)
    yield main, [loss.name]
    main, _, loss = build_conv_bn()
    yield main, [loss.name]
    main, _, loss = build_dp_mlp()     # a data-parallel program
    yield main, [loss.name]


@pytest.fixture(scope="module")
def default_config_counts(tmp_path_factory):
    """Rewrites applied per family over the corpus: every gate at its
    default (no env override, an empty autotune cache), nothing run, only
    ``resolve_fused_program``."""
    from paddle_tpu import autotune

    seen = {}
    with pytest.MonkeyPatch.context() as mp:
        for name in [n for n in os.environ if n.startswith("PADDLE_TPU_")
                     and n != "PADDLE_TPU_VERIFY_PASSES"]:
            mp.delenv(name)     # conftest.py's, or what another file left
        mp.setenv("PADDLE_TPU_AUTOTUNE_CACHE",
                  str(tmp_path_factory.mktemp("autotune") / "empty.json"))
        autotune.reset()
        for programs in (_example_programs, _cell_programs,
                         _this_files_programs):
            for program, targets in programs():
                _, report = fusion.resolve_fused_program(
                    program, targets=targets)
                for family, n in report.counts().items():
                    seen[family] = seen.get(family, 0) + n
    autotune.reset()
    return seen


@pytest.mark.parametrize("family", [row[0] for row in fusion._FAMILIES])
def test_every_family_fires_under_the_default_config(
        default_config_counts, family):
    """A family that no default configuration reaches is a dead fork
    (as the embedding-gather and optimizer families were): matched in every
    resolve, keyed into every jit key, and kept alive by tests that open
    its gate.  It has to apply somewhere with every gate at its default."""
    assert default_config_counts.get(family, 0) >= 1
