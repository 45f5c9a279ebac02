"""Flash-attention Pallas kernel tests (interpret mode on CPU) and the
fused_multihead_attention op/layer, mirroring the reference OpTest pattern
(`python/paddle/fluid/tests/unittests/op_test.py`): kernel vs XLA-reference
oracle for forward and grads."""

import collections
import os

import numpy as np
import pytest

os.environ.setdefault("PADDLE_TPU_PALLAS", "interpret")

import jax
import jax.numpy as jnp

import importlib

# the package __init__ re-exports the flash_attention *function* under the
# same name as the module, so resolve the module explicitly
FA = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")


def _rand(rng, *shape):
    return jnp.asarray(rng.randn(*shape).astype("float32"))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("with_bias", [False, True])
def test_kernel_fwd_bwd_single_block(causal, with_bias):
    rng = np.random.RandomState(0)
    B, H, T, D = 2, 2, 128, 64
    q, k, v = (_rand(rng, B, H, T, D) for _ in range(3))
    bias = None
    if with_bias:
        bias = jnp.asarray(
            np.where(rng.rand(B, T) < 0.2, -1e4, 0).astype("float32")
        )

    o1 = FA.flash_attention(q, k, v, bias=bias, causal=causal)
    o2 = FA.mha_reference(q, k, v, bias=bias, causal=causal)
    np.testing.assert_allclose(o1, o2, atol=2e-5, rtol=2e-5)

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v, bias=bias, causal=causal) * v
        )

    g1 = jax.grad(loss(FA.flash_attention), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(FA.mha_reference), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=1e-3)


def test_kernel_multiblock(monkeypatch):
    """Multiple KV/Q blocks exercise the online-softmax accumulation and
    the bwd sweep accumulators (plus the big-|bias| precision path that
    motivated saving (m, l) instead of lse)."""
    monkeypatch.setattr(FA, "_pick_blocks", lambda tq, tk: (64, 128))
    rng = np.random.RandomState(1)
    B, H, T, D = 2, 2, 256, 64
    q, k, v = (_rand(rng, B, H, T, D) for _ in range(3))
    bias = jnp.asarray(
        np.where(rng.rand(B, T) < 0.2, -1e4, 0).astype("float32")
    )
    for causal in (False, True):
        o1 = FA.flash_attention(q, k, v, bias=bias, causal=causal)
        o2 = FA.mha_reference(q, k, v, bias=bias, causal=causal)
        np.testing.assert_allclose(o1, o2, atol=2e-5, rtol=2e-5)
    g1 = jax.grad(
        lambda q, k, v: jnp.sum(
            FA.flash_attention(q, k, v, bias=bias, causal=True) * v
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    g2 = jax.grad(
        lambda q, k, v: jnp.sum(
            FA.mha_reference(q, k, v, bias=bias, causal=True) * v
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=1e-3)


def test_kernel_bf16_inputs_match_reference():
    """bf16 (AMP) inputs: the kernel now feeds the MXU input-dtype
    operands with f32 accumulation — QK^T is bit-identical to the old
    upcast form (bf16 casts are exact, 8-bit-mantissa products fit
    f32), and the PV/backward downcasts match mha_reference's own
    (bf16-scaled tolerances)."""
    rng = np.random.RandomState(5)
    B, H, T, D = 2, 2, 256, 64
    q, k, v = (jnp.asarray(rng.randn(B, H, T, D), jnp.bfloat16)
               for _ in range(3))
    o1 = FA.flash_attention(q, k, v)
    o2 = FA.mha_reference(q, k, v)
    np.testing.assert_allclose(
        np.asarray(o1, np.float32), np.asarray(o2, np.float32),
        atol=2e-2, rtol=2e-2)

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v).astype(jnp.float32) ** 2)

    g1 = jax.grad(loss(FA.flash_attention), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(FA.mha_reference), argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=0.5, rtol=6e-2, err_msg="d%s" % nm)


def test_fused_op_in_program():
    """Program-level: fused_multihead_attention layer vs the unfused op
    chain, both through the Executor, gradients included.  T=128 so the
    Pallas kernel path (interpret mode) actually engages — this covers the
    registry's generic jax.vjp grad over the kernel's custom_vjp."""
    import paddle_tpu as fluid
    # the package must expose the SUBMODULE under this name (a
    # function re-export here once shadowed it and broke every
    # module-path import — see ops/pallas/__init__.py)
    from paddle_tpu.ops.pallas import flash_attention as _fa_mod

    assert _fa_mod is FA and callable(_fa_mod.flash_attention)

    assert FA._kernel_applicable(
        jnp.zeros((4, 128, 16)), jnp.zeros((4, 128, 16)), None
    ), "test shapes must exercise the kernel path"

    B, H, T, D = 1, 2, 128, 16
    rng = np.random.RandomState(2)
    qv = rng.randn(B, H, T, D).astype("float32")
    kv = rng.randn(B, H, T, D).astype("float32")
    vv = rng.randn(B, H, T, D).astype("float32")

    def build(fused):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            q = fluid.layers.data("q", shape=[H, T, D])
            k = fluid.layers.data("k", shape=[H, T, D])
            v = fluid.layers.data("v", shape=[H, T, D])
            q.stop_gradient = False
            if fused:
                out = fluid.layers.fused_multihead_attention(q, k, v)
            else:
                s = fluid.layers.matmul(
                    q, k, transpose_y=True, alpha=1.0 / np.sqrt(D)
                )
                p = fluid.layers.softmax(s)
                out = fluid.layers.matmul(p, v)
            loss = fluid.layers.reduce_sum(out)
            grads = fluid.backward.gradients([loss], [q])
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        return exe.run(
            main, feed={"q": qv, "k": kv, "v": vv},
            fetch_list=[out, grads[0]],
        )

    o_f, gq_f = build(True)
    o_u, gq_u = build(False)
    np.testing.assert_allclose(o_f, o_u, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(gq_f, gq_u, atol=1e-4, rtol=1e-4)


class TestInKernelDropout:
    """Debug-hash mode (PADDLE_TPU_FLASH_DROPOUT_DEBUG=iota): the kernel
    and the XLA reference draw the IDENTICAL mask, so fwd outputs and all
    grads must match to float tolerance — verifying the FA2 dropout math
    (l from undropped p, masked numerator, mask-scaled dP in backward)
    independently of the hardware PRNG."""

    def setup_method(self):
        os.environ["PADDLE_TPU_FLASH_DROPOUT_DEBUG"] = "iota"

    def teardown_method(self):
        os.environ.pop("PADDLE_TPU_FLASH_DROPOUT_DEBUG", None)

    @pytest.mark.parametrize("rate", [0.1, 0.5])
    @pytest.mark.parametrize("multiblock", [False, True])
    def test_fwd_bwd_match_reference(self, rate, multiblock):
        rng = np.random.RandomState(0)
        B, H, D = 2, 2, 64
        T = 512 if multiblock else 128
        q = _rand(rng, B, H, T, D)
        k = _rand(rng, B, H, T, D)
        v = _rand(rng, B, H, T, D)
        seed = 1234

        if multiblock:
            bq, bk = 128, 256
        else:
            bq, bk = T, max(128, T)

        def flash_loss(q, k, v):
            qf = q.reshape(B * H, T, D)
            kf = k.reshape(B * H, T, D)
            vf = v.reshape(B * H, T, D)
            o = FA._flash(qf, kf, vf, None,
                          jnp.asarray([seed], jnp.int32), False,
                          1.0 / np.sqrt(D), bq, bk, True, rate, True)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        def ref_loss(q, k, v):
            o = FA.mha_reference(q, k, v, sm_scale=1.0 / np.sqrt(D),
                                 dropout_rate=rate,
                                 seed=jnp.asarray([seed], jnp.int32),
                                 debug=True)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        lf, gf = jax.value_and_grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
        lr_, gr = jax.value_and_grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        # Tolerance rationale: inputs here are f32, so both paths compute
        # f32 apart from kernel-vs-XLA reduction-order differences —
        # 2e-5/2e-4 bounds those.  mha_reference downcasts the dropout-
        # scaled probabilities to q.dtype before the PV matmul (the MXU-
        # rate tradeoff); under bf16 AMP that widens the gap, which the
        # program-level AMP tests cover with bf16-scaled bounds instead.
        np.testing.assert_allclose(float(lf), float(lr_), rtol=2e-5)
        for a, b, nm in zip(gf, gr, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4,
                err_msg="d%s mismatch" % nm)

    def test_mask_actually_drops(self):
        """Dropout changes the output vs rate=0 and zero cells appear at
        the hash-predicted positions."""
        rng = np.random.RandomState(1)
        B, H, T, D = 1, 1, 128, 64
        q = _rand(rng, B, H, T, D)
        k = _rand(rng, B, H, T, D)
        v = _rand(rng, B, H, T, D)
        seed = jnp.asarray([7], jnp.int32)
        o_drop = FA._flash(q.reshape(1, T, D), k.reshape(1, T, D),
                           v.reshape(1, T, D), None, seed, False,
                           1.0 / np.sqrt(D), T, 128, True, 0.5, True)
        o_plain = FA._flash(q.reshape(1, T, D), k.reshape(1, T, D),
                            v.reshape(1, T, D), None, seed, False,
                            1.0 / np.sqrt(D), T, 128, True, 0.0, True)
        assert not np.allclose(np.asarray(o_drop), np.asarray(o_plain))
        # keep fraction of the debug hash is ~1-rate
        keep = np.asarray(FA.debug_keep_mask(1, T, T, 0.5, 7))
        assert abs(keep.mean() - 0.5) < 0.05

    def test_dropout_through_program(self):
        """attn_dropout>0 BERT config now takes the fused path and trains
        (loss finite and decreasing)."""
        import paddle_tpu as fluid
        from paddle_tpu.models import bert

        cfg = bert.BertConfig(vocab_size=256, hidden=64, layers=1,
                              heads=2, ffn=128, max_seq=128, dropout=0.1,
                              fuse_attn=True)
        assert cfg.attn_dropout == 0.1
        main, startup, feeds, loss = bert.build_pretrain(
            cfg, seq_len=128, lr=1e-3, train=True)
        fused_ops = [op for op in main.global_block().ops
                     if op.type == "fused_multihead_attention"]
        assert fused_ops and fused_ops[0].attr("dropout_rate") == 0.1
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        rng = np.random.RandomState(0)
        feed = bert.make_fake_batch(4, 128, cfg, rng)
        l0 = float(np.asarray(exe.run(
            main, feed=feed, fetch_list=[loss])[0]).reshape(()))
        for _ in range(6):
            exe.run(main, feed=feed, fetch_list=[])
        l1 = float(np.asarray(exe.run(
            main, feed=feed, fetch_list=[loss])[0]).reshape(()))
        assert np.isfinite(l0) and np.isfinite(l1)
        assert l1 < l0


    def test_clone_for_test_disables_kernel_dropout(self):
        """clone(for_test=True) must switch in-kernel dropout off — the
        serving path has no other off-switch for the fused op."""
        import paddle_tpu as fluid
        from paddle_tpu.models import bert

        cfg = bert.BertConfig(vocab_size=256, hidden=64, layers=1,
                              heads=2, ffn=128, max_seq=128, dropout=0.1,
                              fuse_attn=True)
        fluid.unique_name.switch()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            ids = fluid.layers.data("input_ids", shape=[128],
                                    dtype="int64")
            tt = fluid.layers.data("token_type_ids", shape=[128],
                                   dtype="int64")
            mb = fluid.layers.data("attn_mask_bias", shape=[1, 1, 128],
                                   dtype="float32")
            x = bert.encoder(ids, tt, mb, cfg, 128)
            out = fluid.layers.reduce_mean(x)
        test_prog = main.clone(for_test=True)
        fused = [op for op in test_prog.global_block().ops
                 if op.type == "fused_multihead_attention"]
        assert fused and all(op.attr("is_test") for op in fused)
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        rng = np.random.RandomState(0)
        feed = {k: v for k, v in bert.make_fake_batch(
            2, 128, cfg, rng).items()
            if k in ("input_ids", "token_type_ids", "attn_mask_bias",
                     "pos_ids")}
        o1 = exe.run(test_prog, feed=feed, fetch_list=[out])[0]
        o2 = exe.run(test_prog, feed=feed, fetch_list=[out])[0]
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))

    def test_rate_validation(self):
        rng = np.random.RandomState(0)
        q = _rand(rng, 1, 1, 128, 64)
        with pytest.raises(ValueError, match="dropout_rate"):
            FA.flash_attention(q, q, q, dropout_rate=1.0, dropout_seed=1)


# -- the grid stops at the diagonal; masks only where it crosses -------------

def _fwd_and_grads(fn, q, k, v, w, **kw):
    out = fn(q, k, v, **kw)
    grads = jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v, **kw) * w),
                     argnums=(0, 1, 2))(q, k, v)
    return (out,) + grads


def _against_reference(q, k, v, **kw):
    """Forward and the three gradients of the kernels against
    ``mha_reference``, at the tolerances of the tests above."""
    w = jnp.asarray(np.random.RandomState(9).randn(
        *q.shape[:3], v.shape[-1]).astype("float32"))
    assert FA.routes_to_kernel(q, k, kw.get("bias"), v)
    got = _fwd_and_grads(FA.flash_attention, q, k, v, w, **kw)
    want = _fwd_and_grads(FA.mha_reference, q, k, v, w, **kw)
    np.testing.assert_allclose(got[0], want[0], atol=2e-5, rtol=2e-5)
    for a, b, nm in zip(got[1:], want[1:], "qkv"):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=1e-3,
                                   err_msg="d%s" % nm)
    return got


@pytest.mark.parametrize("t,dqk,dv,bq,bk", [
    (512, 192, 128, 128, 128),   # latent attention's widths, 4 x 4 blocks
    (512, 64, 64, 256, 128),     # the diagonal crosses two K blocks a row
    (512, 64, 64, 128, 256),     # ... two Q blocks a K block
    (384, 64, 64, 64, 128),
    (256, 64, 64, 256, 256),     # tq == bq: one block, masked
    (768, 192, 128, 256, 256),   # square blocks: the diagonal ones in
])                               # two parts, their upper quarter left out
def test_causal_blocks(t, dqk, dv, bq, bk, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FLASH_BLOCK_Q", str(bq))
    monkeypatch.setenv("PADDLE_TPU_FLASH_BLOCK_K", str(bk))
    assert FA._pick_blocks(t, t)[:2] == (bq, bk)
    rng = np.random.RandomState(3)
    q, k = (_rand(rng, 1, 2, t, dqk) for _ in range(2))
    _against_reference(q, k, _rand(rng, 1, 2, t, dv), causal=True)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("bq,bk,span", [(128, 128, 2), (64, 128, 1),
                                        (128, 256, 1)])
def test_a_sweep_of_several_grid_steps(bq, bk, span, causal, monkeypatch):
    """A sequence too long for one grid step to hold its K and V (or Q
    and dO): the sweep goes on over the grid's last axis, the state in
    scratch, and a causal step wholly above the diagonal runs no chunk
    and fetches nothing new."""
    monkeypatch.setenv("PADDLE_TPU_FLASH_BLOCK_Q", str(bq))
    monkeypatch.setenv("PADDLE_TPU_FLASH_BLOCK_K", str(bk))
    monkeypatch.setattr(FA, "_span", lambda n, rows, row_bytes: span)
    rng = np.random.RandomState(6)
    q, k, v = (_rand(rng, 1, 2, 512, 64) for _ in range(3))
    bias = jnp.asarray(np.where(rng.rand(1, 512) < 0.2, -1e4, 0)
                       .astype("float32"))
    _against_reference(q, k, v, causal=causal, bias=bias)


@pytest.mark.parametrize("bk", [128, 256])
def test_statistics_carry_across_k_blocks_with_bias_and_dropout(
        bk, monkeypatch):
    """Not causal, a key bias, debug-hash dropout, 4 or 2 K blocks a Q
    block: m and l ride the sweep in their lane-replicated form."""
    monkeypatch.setenv("PADDLE_TPU_FLASH_DROPOUT_DEBUG", "iota")
    monkeypatch.setenv("PADDLE_TPU_FLASH_BLOCK_Q", "128")
    monkeypatch.setenv("PADDLE_TPU_FLASH_BLOCK_K", str(bk))
    rng = np.random.RandomState(4)
    b, h, t, d = 2, 2, 512, 64
    q, k, v = (_rand(rng, b, h, t, d) for _ in range(3))
    bias = jnp.asarray(np.where(rng.rand(b, t) < 0.2, -1e4, 0)
                       .astype("float32"))
    w = jnp.asarray(rng.randn(b, h, t, d).astype("float32"))
    seed = jnp.asarray([77], jnp.int32)
    got = _fwd_and_grads(FA.flash_attention, q, k, v, w, bias=bias,
                         dropout_rate=0.1, dropout_seed=seed)
    want = _fwd_and_grads(FA.mha_reference, q, k, v, w, bias=bias,
                          dropout_rate=0.1, seed=seed, debug=True)
    np.testing.assert_allclose(got[0], want[0], atol=2e-5, rtol=2e-5)
    for a, b_, nm in zip(got[1:], want[1:], "qkv"):
        np.testing.assert_allclose(a, b_, atol=2e-4, rtol=2e-4,
                                   err_msg="d%s" % nm)


def test_causal_with_bias_and_dropout_on_square_blocks(monkeypatch):
    """The diagonal blocks' two parts take their slices of the block's
    one dropout draw and of the key bias."""
    monkeypatch.setenv("PADDLE_TPU_FLASH_DROPOUT_DEBUG", "iota")
    monkeypatch.setenv("PADDLE_TPU_FLASH_BLOCK_Q", "256")
    monkeypatch.setenv("PADDLE_TPU_FLASH_BLOCK_K", "256")
    rng = np.random.RandomState(8)
    b, h, t, d = 2, 2, 512, 64
    q, k, v = (_rand(rng, b, h, t, d) for _ in range(3))
    bias = jnp.asarray(np.where(rng.rand(b, t) < 0.2, -1e4, 0)
                       .astype("float32"))
    w = jnp.asarray(rng.randn(b, h, t, d).astype("float32"))
    seed = jnp.asarray([5], jnp.int32)
    got = _fwd_and_grads(FA.flash_attention, q, k, v, w, bias=bias,
                         causal=True, dropout_rate=0.1, dropout_seed=seed)
    want = _fwd_and_grads(FA.mha_reference, q, k, v, w, bias=bias,
                          causal=True, dropout_rate=0.1, seed=seed,
                          debug=True)
    np.testing.assert_allclose(got[0], want[0], atol=2e-5, rtol=2e-5)
    for a, b_, nm in zip(got[1:], want[1:], "qkv"):
        np.testing.assert_allclose(a, b_, atol=2e-4, rtol=2e-4,
                                   err_msg="d%s" % nm)


def test_fully_masked_rows_are_finite(monkeypatch):
    """A bias of -1e30 on every key leaves a row nothing to prefer: the
    kernels give what the reference gives (the mean of V), no NaN, in
    the output and in all three gradients."""
    monkeypatch.setenv("PADDLE_TPU_FLASH_BLOCK_Q", "128")
    monkeypatch.setenv("PADDLE_TPU_FLASH_BLOCK_K", "128")
    rng = np.random.RandomState(5)
    q, k, v = (_rand(rng, 2, 2, 256, 64) for _ in range(3))
    bias = jnp.asarray(np.stack([np.full(256, -1e30), np.zeros(256)])
                       .astype("float32"))
    out = _against_reference(q, k, v, bias=bias)[0]
    np.testing.assert_allclose(out[0], jnp.broadcast_to(
        jnp.mean(v[0], axis=1, keepdims=True), out[0].shape), atol=2e-5)


# -- the set-up budget, without a clock --------------------------------------

def _count_equations(jaxpr):
    """Equations of a jaxpr, those of nested jaxprs included."""
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for val in eqn.params.values():
            for sub in val if isinstance(val, (list, tuple)) else [val]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _count_equations(sub)
    return n


# about three times the 83 / 65 / 55 of the kernels before PR 33
BODY_LIMIT = {"flash_attention_fwd": 250, "flash_attention_dkv": 200,
              "flash_attention_dq": 170}


@pytest.mark.parametrize("bh,t,dqk,dv,causal,bias,rate", [
    (128, 4096, 192, 128, True, False, 0.0),    # the kanana cell's site
    (384, 512, 64, 64, False, True, 0.1),       # BERT seq512's
])
def test_one_small_kernel_a_site(bh, t, dqk, dv, causal, bias, rate):
    """``_flash_fwd`` holds ONE ``pallas_call``, ``_flash_bwd`` one for
    dK/dV and one for dQ, whatever the number of blocks, and each body
    stays small: every attention site's kernels are traced when the
    Program is built and traced and lowered again at the first step, so
    a body unrolled over blocks, or a call a row of blocks, is paid
    twice a site in ``setup_s`` (PR 32's refusal)."""
    bq, bk = FA._pick_blocks(t, t)[:2]
    assert t // bq == (8 if causal else 1)
    sd = jax.ShapeDtypeStruct
    q, v = sd((bh, t, dqk), jnp.bfloat16), sd((bh, t, dv), jnp.bfloat16)
    b = sd((bh // 12, t), jnp.float32) if bias else None
    seed, stat = sd((1,), jnp.int32), sd((bh, 1, t), jnp.float32)
    static = (causal, 0.125, bq, bk, False, rate, False)
    fwd = jax.make_jaxpr(lambda q, k, v, b, s: FA._flash_fwd(
        q, k, v, b, s, *static))(q, q, v, b, seed)
    bwd = jax.make_jaxpr(lambda q, k, v, b, s, o, m, l, do: FA._flash_bwd(
        q, k, v, b, s, o, m, l, do, *static))(q, q, v, b, seed, v, stat,
                                              stat, v)
    calls = [(e.params["name"], _count_equations(e.params["jaxpr"]))
             for jp in (fwd, bwd) for e in jp.jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    assert [name for name, _ in calls] == list(BODY_LIMIT)
    for name, n in calls:
        assert n < BODY_LIMIT[name], (name, n)


# -- how often the mechanism engages -----------------------------------------

def test_blocks_noted_at_the_kanana_site():
    """One head of 4096 at 512 x 512 blocks, causal: each kernel's grid
    visits 36 of the 64 blocks and masks 8 of the 36."""
    sd = jax.ShapeDtypeStruct
    q, v = sd((1, 1, 4096, 192), jnp.bfloat16), sd((1, 1, 4096, 128),
                                                   jnp.bfloat16)
    assert FA._pick_blocks(4096, 4096)[:2] == (512, 512)
    with FA.noting_blocks(collections.Counter()) as noted:
        jax.eval_shape(jax.grad(lambda q, k, v: jnp.sum(FA.flash_attention(
            q, k, v, causal=True).astype(jnp.float32)), argnums=(0, 1, 2)),
            q, q, v)
    assert noted == {(kernel, kind): n for kernel in ("fwd", "dkv", "dq")
                     for kind, n in (("possible", 64), ("visited", 36),
                                     ("masked", 8))}
    with FA.noting_blocks(collections.Counter()) as noted:
        jax.eval_shape(FA.flash_attention, q, q, v)     # not causal
    assert noted == {("fwd", "possible"): 64, ("fwd", "visited"): 64,
                     ("fwd", "masked"): 0}


def test_compile_phase_carries_the_blocks(monkeypatch):
    """A training step with one causal site of 2 x 2 blocks over 6
    batch-heads: the ``compile`` phase holds the three kernels' blocks,
    and the counter has them by kernel."""
    import paddle_tpu as fluid
    from paddle_tpu.observability import metrics, tracing

    monkeypatch.setenv("PADDLE_TPU_FLASH_BLOCK_Q", "128")
    monkeypatch.setenv("PADDLE_TPU_FLASH_BLOCK_K", "128")
    metrics.registry().reset()
    b, h, t, d = 2, 3, 256, 16
    fluid.unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[h, t, d])
        x.stop_gradient = False
        q = fluid.layers.scale(x, scale=0.5)
        out = fluid.layers.fused_multihead_attention(q, q, q, causal=True)
        loss = fluid.layers.reduce_sum(out)
        fluid.backward.gradients([loss], [x])
    exe = fluid.Executor(fluid.CPUPlace())
    with tracing.span("test.root"):     # inside a trace every step records
        exe.run(main, feed={"x": np.ones((b, h, t, d), "float32")},
                fetch_list=[loss])
    attrs = [r["attrs"] for r in tracing.get_tracer().records()
             if r["name"] == "executor.compile"
             and "flash_blocks_possible" in r["attrs"]][-1]
    heads, kernels = b * h, 3
    assert (attrs["flash_blocks_possible"], attrs["flash_blocks_visited"],
            attrs["flash_blocks_masked"]) == (
        kernels * heads * 4, kernels * heads * 3, kernels * heads * 2)
    got = {(dict(m.labels)["kernel"], dict(m.labels)["kind"]): m.value
           for m in metrics.registry().collect()
           if m.name == "flash_blocks_total"}
    assert got[("dkv", "visited")] == heads * 3 and len(got) == 9
