"""The grouped-query decoder (``models/decoder.py`` ``attention: "gqa"``:
sliding and full layers, a YaRN table for the full ones, a softmax top-k
expert layer with no shared expert) against its plain reference
``chipbench/reference/mellum2.py``, at ``MELLUM_TINY`` on the CPU, and the
pieces it forced: the flash kernels with fewer key-value heads than query
heads and a window (interpret mode), the blocks they visit, the rotary
op's frequency scaling, the softmax router, the share of a deployment."""

import collections
import importlib
import math
import os
import sys

import numpy as np
import pytest

os.environ.setdefault("PADDLE_TPU_PALLAS", "interpret")

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import paddle_tpu as fluid  # noqa: E402
import paddle_tpu.ops.pallas as pallas  # noqa: E402
from chipbench import manifest as mf  # noqa: E402
from paddle_tpu.models import decoder  # noqa: E402

REF = mf.load_by_name("reference", "mellum2")
FA = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

# hidden 64, 4 query heads on 2 key-value heads of 16, window 24, three
# sliding layers and a full one, 8 experts top-2, 2 held, vocabulary 256
CFG = dict(decoder.MELLUM_TINY)
T, B = 128, 2


def _feed(seed=0):
    ids = np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], size=(B, T + 1)).astype("int64")
    return {"input_ids": np.ascontiguousarray(ids[:, :-1]),
            "labels": np.ascontiguousarray(ids[:, 1:])}


def _weights(scope=None):
    scope = scope or fluid.global_scope()
    return {n: np.asarray(scope.get(n)) for n in scope.local_var_names()
            if n.startswith("decoder.")}


def _build(train, amp=False, cfg=CFG, backward=False):
    with fluid.unique_name.guard():
        main, startup, _, loss = decoder.build_train(
            cfg, seq_len=T, lr=1e-3, amp=amp, train=train)
        if backward:
            with fluid.program_guard(main, startup):
                fluid.backward.append_backward(loss)
    main.random_seed = startup.random_seed = 5
    return main, startup, loss


def _reference(w, feed, cfg=CFG):
    with jax.default_matmul_precision("highest"):
        return REF.forward(w, feed, cfg)


# -- the model against the reference -------------------------------------------

@pytest.mark.parametrize("recompute", [True, False])
def test_forward_logits_and_loss_match_the_reference(recompute):
    """Through the flash kernels (interpret mode): the window of 24 is no
    multiple of a block, the full layer turns by its YaRN table."""
    cfg = dict(CFG, recompute=recompute)
    main, startup, loss = _build(train=False, cfg=cfg)
    ops = main.global_block().ops if not recompute else [
        op for b in main.blocks for op in b.ops]
    head = [op for op in ops if op.type == "softmax_with_cross_entropy"][0]
    sites = [op for op in ops if op.type == "fused_multihead_attention"]
    assert [op.attrs.get("window") for op in sites] == [24, 24, 24, None]
    feed = _feed()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        logits, got = exe.run(main, feed=feed, fetch_list=[
            head.input("Logits")[0], loss])
        want = _reference(_weights(), feed, cfg)
    np.testing.assert_allclose(logits, want["logits"], atol=2e-5, rtol=2e-4)
    assert abs(float(got[0]) - float(want["loss"])) < 1e-5
    assert abs(float(want["loss"]) - np.log(CFG["vocab_size"])) < 0.1


@pytest.mark.parametrize("name", [
    "decoder.layer1.attn.q.w", "decoder.layer1.attn.k.w",
    "decoder.layer3.attn.v.w", "decoder.layer3.attn.o.w",
    "decoder.layer0.moe.router.w", "decoder.layer2.moe.experts.1.gate",
    "decoder.embed", "decoder.head.w"])
def test_gradient_matches_jax_grad_of_the_reference(name):
    """One parameter of each kind, through ``append_backward``, the
    recompute regions' own ``jax.vjp`` and the dK/dV and dQ kernels."""
    main, startup, loss = _build(train=False, backward=True)
    feed = _feed(1)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        w = _weights()
        got = exe.run(main, feed=feed, fetch_list=[name + "@GRAD"])[0]
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda p: REF.loss(dict(w, **{name: p}), feed,
                                           CFG))(jnp.asarray(w[name]))
    want = np.asarray(want)
    assert np.abs(want).max() > 0
    np.testing.assert_allclose(got, want, atol=2e-6 + 2e-4 * np.abs(
        want).max(), rtol=2e-3)


def test_training_lowers_the_loss_and_the_compile_phase_says_what_it_holds():
    from paddle_tpu.observability import runtime, tracing

    main, startup, loss = _build(train=True, amp=True)
    feed = _feed(3)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        with tracing.span("test.root"):     # inside a trace a step records
            first = float(exe.run(main, feed=feed, fetch_list=[loss])[0][0])
        losses = [first] + [float(exe.run(main, feed=feed,
                                          fetch_list=[loss])[0][0])
                            for _ in range(5)]
        counted = runtime.publish_moe_counters()
    assert losses[-1] < losses[0] - 0.3
    assert sorted(counted) == ["0", "1", "2", "3"]  # no leading dense layer
    attrs = [r["attrs"] for r in tracing.get_tracer().records()
             if r["name"] == "executor.compile"
             and "attention_layers_sliding" in r["attrs"]][-1]
    assert (attrs["attention_layers_sliding"], attrs["attention_layers_full"],
            attrs["kv_heads"]) == (3, 1, 2)
    # T 128 is one block: each kernel of each site visits its one block,
    # masked; three of the four sites have a window
    assert attrs["flash_blocks_possible"] == attrs["flash_blocks_visited"] \
        == attrs["flash_blocks_masked"]
    assert attrs["flash_window_blocks_visited"] * 4 == \
        attrs["flash_blocks_visited"] * 3


def test_an_unknown_attention_kind_or_layer_type_is_refused():
    for cfg in (dict(CFG, attention="differential"),
                dict(CFG, layer_types=["chunked_attention"] * 4)):
        with pytest.raises(ValueError, match="models/decoder.py has no"):
            _build(train=False, cfg=cfg)


# -- the flash kernels: fewer key-value heads, a window -----------------------

def _rand(rng, *shape):
    return jnp.asarray(rng.randn(*shape).astype("float32"))


def _fwd_and_grads(fn, q, k, v, w, **kw):
    out = fn(q, k, v, **kw)
    grads = jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v, **kw) * w),
                     argnums=(0, 1, 2))(q, k, v)
    return (out,) + grads


@pytest.mark.parametrize("t,h,hkv,bq,bk,window,span", [
    (512, 4, 2, 128, 128, 256, None),   # a multiple of the blocks
    (512, 4, 2, 128, 128, 200, None),   # not one
    (512, 4, 1, 128, 128, 129, None),   # one key past a block; one kv head
    (512, 4, 2, 64, 128, 130, None),    # block_q != block_k
    (512, 4, 2, 128, 256, 300, None),
    (512, 4, 2, 256, 256, 300, None),   # square blocks: _tiles on the diagonal
    (512, 4, 2, 128, 128, 1, None),     # the query's own key alone
    (512, 4, 2, 128, 128, 4096, None),  # wider than the sequence: causal
    (512, 8, 2, 128, 128, None, None),  # no window, groups of 4
    (1024, 4, 2, 128, 128, 256, 2),     # several spans a sweep
    (1024, 4, 2, 128, 128, 300, 1),
    (1024, 4, 2, 128, 128, None, 2),
])
def test_grouped_heads_and_window_match_the_reference(
        t, h, hkv, bq, bk, window, span, monkeypatch):
    """Forward, dQ, and dK and dV summed over each group's query heads,
    against ``mha_reference`` (which takes the same arguments)."""
    monkeypatch.setenv("PADDLE_TPU_FLASH_BLOCK_Q", str(bq))
    monkeypatch.setenv("PADDLE_TPU_FLASH_BLOCK_K", str(bk))
    if span:
        monkeypatch.setattr(FA, "_span", lambda n, rows, row_bytes: span)
    rng = np.random.RandomState(3)
    q = _rand(rng, 2, h, t, 64)
    k, v = _rand(rng, 2, hkv, t, 64), _rand(rng, 2, hkv, t, 32)
    w = _rand(rng, 2, h, t, 32)
    assert FA.routes_to_kernel(q, k, None, v)
    kw = dict(causal=True, window=window)
    got = _fwd_and_grads(FA.flash_attention, q, k, v, w, **kw)
    want = _fwd_and_grads(FA.mha_reference, q, k, v, w, **kw)
    assert got[2].shape == k.shape and got[3].shape == v.shape
    np.testing.assert_allclose(got[0], want[0], atol=2e-5, rtol=2e-5)
    for a, b, nm in zip(got[1:], want[1:], "qkv"):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=1e-3,
                                   err_msg="d%s" % nm)


def test_the_window_sees_its_own_keys_and_no_others():
    """``q = 0`` and ``v`` one-hot at key j0: row i of the output is 1/n_i
    where j0 is visible to i and exactly 0 elsewhere (the probe
    ``chip_smoke.py`` runs at the cell's shapes on the chip)."""
    t, window = 512, 200
    for j0 in (0, 199, 200, 311):
        q = jnp.zeros((1, 2, t, 64))
        v = jnp.zeros((1, 1, t, 64)).at[0, 0, j0].set(1.0)
        out = np.asarray(FA.flash_attention(q, q[:, :1], v, causal=True,
                                            window=window))[0, :, :, 0]
        rows = np.arange(t)
        seen = (rows >= j0) & (rows - j0 < window)
        assert ((out != 0) == seen[None, :]).all()
        np.testing.assert_allclose(
            out[:, seen], np.broadcast_to(
                1.0 / np.minimum(rows[seen] + 1, window), (2, seen.sum())),
            rtol=1e-6)


def test_mixed_kinds_of_mask_are_refused():
    q = jnp.zeros((1, 4, 128, 16))
    with pytest.raises(ValueError, match="causal"):
        FA.flash_attention(q, q, q, window=8)
    with pytest.raises(ValueError, match="divide"):
        FA.flash_attention(q, q[:, :3], q[:, :3], causal=True)


def _noted(t, h, hkv, window, grad=True):
    sd = jax.ShapeDtypeStruct
    q, k = sd((1, h, t, 128), jnp.bfloat16), sd((1, hkv, t, 128),
                                                jnp.bfloat16)

    def fn(q, k, v):
        return jnp.sum(FA.flash_attention(
            q, k, v, causal=True, window=window).astype(jnp.float32))

    with FA.noting_blocks(collections.Counter()) as noted:
        jax.eval_shape(jax.grad(fn, argnums=(0, 1, 2)) if grad else fn,
                       q, k, k)
    return noted


def test_blocks_visited_under_the_window_are_the_set_worked_by_hand():
    """One sequence of 8192 at 512 x 512 blocks, 32 query heads on 4,
    window 1024: Q block i sees K blocks i-2 (the edge crosses it), i-1
    (whole) and i (the diagonal): 1 + 2 + 14 x 3 = 45 of 256 a head,
    30 + 1 masked (the edge's 14, the diagonal's 16, block 1's... both);
    a K block's Q blocks are the same pairs.  Without the window: 136 of
    256, 16 masked."""
    assert FA._pick_blocks(8192, 8192)[:2] == (512, 512)
    by_hand = {(i, j) for i in range(16) for j in range(16)
               if j <= i and 512 * i - (512 * j + 511) < 1024}
    assert len(by_hand) == 45
    masked = {(i, j) for i, j in by_hand
              if j == i or 512 * i + 511 - 512 * j >= 1024}
    assert len(masked) == 16 + 14
    noted = _noted(8192, 32, 4, 1024)
    want = {(kernel, pre + kind): 32 * n
            for kernel in ("fwd", "dkv", "dq") for pre in ("", "window_")
            for kind, n in (("possible", 256), ("visited", 45),
                            ("masked", 30))}
    assert noted == want
    full = _noted(8192, 32, 4, None)
    assert full == {(kernel, kind): 32 * n
                    for kernel in ("fwd", "dkv", "dq")
                    for kind, n in (("possible", 256), ("visited", 136),
                                    ("masked", 16))}


@pytest.mark.parametrize("window", [1024, 700, 1, None])
def test_each_sweep_runs_just_the_chunks_noted(window, monkeypatch):
    """The loops' own bounds (``_k_sweep``, ``_q_sweep``: what the kernels
    run) against ``_note_blocks``' count (what the counters say), for
    every block of a 2048 sequence at 256 x 256 and 128 x 256 blocks in
    spans of 4 chunks: the chunks a sweep runs are the blocks with a
    visible pair, masked where the diagonal or the edge crosses."""
    for bq, bk in ((256, 256), (128, 256), (256, 128)):
        nq, nk, band = 2048 // bq, 2048 // bk, window or math.inf

        def by_hand(i, j):
            visible = j * bk <= i * bq + bq - 1 and \
                i * bq - (j * bk + bk - 1) < band
            crossed = j * bk + bk - 1 > i * bq or \
                i * bq + bq - 1 - j * bk >= band
            return visible, crossed

        for kernel in ("k", "q"):
            span = 4
            run = {}
            for a in range(nq if kernel == "k" else nk):
                for major in range((nk if kernel == "k" else nq) // span):
                    sweep = FA._k_sweep if kernel == "k" else FA._q_sweep
                    for masked, *bounds in sweep(
                            True, window, a, major, span, bq, bk):
                        for c in [c for lo, hi in zip(bounds[::2],
                                                      bounds[1::2])
                                  for c in range(int(lo), int(hi))]:
                            pair = (a, major * span + c) if kernel == "k" \
                                else (major * span + c, a)
                            assert pair not in run      # no chunk twice
                            run[pair] = bool(masked)
            want = {(i, j): by_hand(i, j)[1] for i in range(nq)
                    for j in range(nk) if by_hand(i, j)[0]}
            # a chunk may run masked though whole (never the reverse)
            assert set(run) == set(want), (bq, bk, kernel)
            assert all(run[p] or not want[p] for p in want)
            assert sum(run.values()) == sum(want.values())


def _kernel_calls(fn, *args):
    jaxpr = jax.make_jaxpr(fn)(*args)
    return [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]


@pytest.fixture
def as_on_chip(monkeypatch):
    """The gating predicate's on-chip answer, so that a jaxpr holds the
    kernels as the chip's step does (nothing is lowered or run)."""
    monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
    monkeypatch.setattr(pallas, "device_platform", lambda: "tpu")


def test_k_and_v_are_never_expanded_to_the_query_heads(as_on_chip):
    """In the traced forward and backward of the cell's site nothing
    outside the kernels that is made from K or V is larger than K or V,
    and the kernels take K and V (and give dK and dV) at 4 heads."""
    sd = jax.ShapeDtypeStruct
    b, h, hkv, t, d = 2, 32, 4, 8192, 128
    q, k = sd((b, h, t, d), jnp.bfloat16), sd((b, hkv, t, d), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
        FA.flash_attention(q, k, v, causal=True, window=1024).astype(
            jnp.float32)), argnums=(0, 1, 2)))(q, k, k).jaxpr
    from_kv = set(jaxpr.invars[1:])
    calls = []
    for eqn in jaxpr.eqns:
        touched = [v for v in eqn.invars
                   if not hasattr(v, "val") and v in from_kv]
        if eqn.primitive.name == "pallas_call":
            calls.append(eqn)
            assert len(touched) == 2
            assert all(v.aval.shape == (b * hkv, t, d) for v in touched)
            continue                # what a kernel gives is the kernel's
        if touched:
            for out in eqn.outvars:
                assert math.prod(out.aval.shape) <= b * hkv * t * d, eqn
                from_kv.add(out)
    assert [c.params["name"] for c in calls] == [
        "flash_attention_fwd", "flash_attention_dkv", "flash_attention_dq"]
    assert [o.aval.shape for o in calls[1].outvars] == [(b * hkv, t, d)] * 2
    assert [o.aval.shape for o in jaxpr.outvars] == [
        (b, h, t, d), (b, hkv, t, d), (b, hkv, t, d)]


def _count_equations(jaxpr):
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for val in eqn.params.values():
            for sub in val if isinstance(val, (list, tuple)) else [val]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _count_equations(sub)
    return n


# tests/test_flash_attention.py BODY_LIMIT: a site's set-up budget
BODY_LIMIT = {"flash_attention_fwd": 250, "flash_attention_dkv": 200,
              "flash_attention_dq": 170}


@pytest.mark.parametrize("window", [1024, None])
def test_one_small_kernel_a_site_with_groups_and_a_window(window,
                                                          as_on_chip):
    """The cell's two kinds of site: still ONE ``pallas_call`` a kernel
    and one small body (two loops over one step: the chunks a window's
    edge crosses share the diagonal's loop), whatever the heads'
    grouping."""
    sd = jax.ShapeDtypeStruct
    q = sd((2, 32, 8192, 128), jnp.bfloat16)
    k = sd((2, 4, 8192, 128), jnp.bfloat16)
    calls = _kernel_calls(jax.grad(lambda q, k, v: jnp.sum(
        FA.flash_attention(q, k, v, causal=True, window=window).astype(
            jnp.float32)), argnums=(0, 1, 2)), q, k, k)
    assert [c.params["name"] for c in calls] == list(BODY_LIMIT)
    for c in calls:
        n = _count_equations(c.params["jaxpr"])
        assert n < BODY_LIMIT[c.params["name"]], (c.params["name"], n)
    grids = [c.params["grid_mapping"].grid for c in calls]
    # dK/dV: a key-value head, a K block, the group's 8 heads x 2 spans
    assert grids == [(64, 16, 2), (8, 16, 16), (64, 16, 2)]


def test_equal_heads_and_no_window_trace_to_the_same_kernels(as_on_chip):
    """``window=None, Hkv == H``: the same jaxpr, kernels' bodies and
    block specs included, as a call that names neither; and a window as
    long as the sequence is the causal mask."""
    sd = jax.ShapeDtypeStruct
    q, v = sd((4, 32, 4096, 192), jnp.bfloat16), sd((4, 32, 4096, 128),
                                                    jnp.bfloat16)

    def traced(**kw):
        return str(jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
            FA.flash_attention(q, k, v, causal=True, **kw).astype(
                jnp.float32)), argnums=(0, 1, 2)))(q, q, v))

    plain = traced()
    assert traced(window=None) == plain == traced(window=4096)
    assert traced(window=4095) != plain
    assert "rem" not in plain and " and " not in plain  # no group, no band


# -- the rotary table, the router, the share ----------------------------------

MELLUM_YARN = {
    "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
    "original_max_position_embeddings": 8192, "beta_fast": 32,
    "beta_slow": 1, "attention_factor": 1.2772588722239782}


def test_yarn_frequencies_against_numbers_worked_by_hand():
    """c(32) = 128 ln(8192 / 64 pi) / (2 ln 500000) = 18.08, c(1) = 34.98:
    low 18, high 35; pairs 0..18 keep their frequency (f'_0 = 1), pairs
    35..63 turn 16 times slower (f'_63 = 500000^(-126/128) / 16), pair 19
    is 1/17 of the way; cos and sin carry 1.2772588722239782."""
    scale, magnitude = decoder.rotary_table(MELLUM_YARN, 128)
    assert magnitude == 1.2772588722239782 and len(scale) == 64
    assert scale[:19] == [1.0] * 19 and scale[35:] == [1 / 16] * 29
    assert scale[19] == pytest.approx(1 - (1 / 17) * (15 / 16))
    f, factor = REF.frequencies(MELLUM_YARN, 128)
    assert factor == magnitude
    assert float(f[0]) == 1.0
    assert float(f[63]) == pytest.approx(500000 ** (-126 / 128) / 16,
                                         rel=1e-6)
    plain = 500000.0 ** (-2 * np.arange(64) / 128)
    np.testing.assert_allclose(f, plain * np.asarray(scale), rtol=1e-6)
    assert decoder.rotary_table({"rope_type": "default",
                                 "rope_theta": 500000}, 128) == (None, 1.0)
    with pytest.raises(ValueError, match="rope_type"):
        decoder.rotary_table({"rope_type": "llama3"}, 128)


def test_the_rotary_op_scales_frequencies_and_magnitude():
    """``layers.rotary_embedding(frequency_scale=, magnitude=)`` on halves
    against the reference's table, position by position."""
    scale, magnitude = decoder.rotary_table(MELLUM_YARN, 128)
    x = np.random.default_rng(2).normal(size=(1, 2, 96, 128)).astype(
        "float32")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        xin = fluid.layers.data("x", shape=[2, 96, 128], dtype="float32")
        out = fluid.layers.rotary_embedding(
            xin, theta=500000, interleaved=False, frequency_scale=scale,
            magnitude=magnitude)
        with pytest.raises(ValueError, match="64 pairs"):
            fluid.layers.rotary_embedding(xin, frequency_scale=[1.0] * 3)
    with fluid.scope_guard(fluid.Scope()):
        got = fluid.Executor(fluid.CPUPlace()).run(
            main, feed={"x": x}, fetch_list=[out])[0]
    want = REF._rotary(jnp.asarray(x).transpose(0, 2, 1, 3), MELLUM_YARN)
    np.testing.assert_allclose(got, np.asarray(want).transpose(0, 2, 1, 3),
                               atol=1e-5, rtol=1e-5)
    # a full layer's scores carry the factor's square
    assert np.linalg.norm(got) / np.linalg.norm(x) == pytest.approx(
        magnitude, rel=1e-5)


def _expert_layer_program(shares, score_func="softmax"):
    """One expert layer over [B, T, 64] with the held experts of each of
    ``shares`` ((first, held) pairs) as ``moe_experts`` ops of their own
    and one softmax router.  Returns what to fetch: the router's index
    and gates, each share's routed part, each share's rows."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[T, CFG["hidden_size"]],
                              dtype="float32")
        init = fluid.initializer.Normal(0.0, 0.3)
        index, gate = fluid.layers.moe_route(
            x, CFG["n_routed_experts"], CFG["num_experts_per_tok"],
            score_func=score_func,
            param_attr=fluid.ParamAttr(name="l.router.w", initializer=init),
            bias_attr=fluid.ParamAttr(
                name="l.router.b",
                initializer=fluid.initializer.Normal(0.0, 0.01)))
        parts, rows = [], []
        for first, held in shares:
            y, r = fluid.layers.moe_experts(
                x, index, gate, CFG["moe_intermediate_size"], held,
                first_expert=first, param_attr=fluid.ParamAttr(
                    name="l.share%d" % first, initializer=init))
            parts.append(y)
            rows.append(r)
    return main, startup, (index, gate), parts, rows


def _uncut_weights(scope, shares):
    """The shares' experts under the names of one layer that holds them
    all, in the order of the shares."""
    w = {n: np.asarray(scope.get(n)) for n in scope.local_var_names()
         if n.startswith("l.")}
    e = 0
    for first, held in shares:
        for local in range(held):
            for k in ("gate", "up", "down"):
                w["l.experts.%d.%s" % (e, k)] = w[
                    "l.share%d.%d.%s" % (first, local, k)]
            e += 1
    return w


def test_softmax_routing_matches_the_reference():
    main, startup, routed, _, _ = _expert_layer_program([(0, 2)])
    x = np.random.default_rng(6).normal(
        size=(B, T, CFG["hidden_size"])).astype("float32")
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        index, gate = exe.run(main, feed={"x": x}, fetch_list=list(routed))
        w = _uncut_weights(fluid.global_scope(), [(0, 2)])
    with jax.default_matmul_precision("highest"):
        idx, g = REF.route(jnp.asarray(x).reshape(-1, x.shape[-1]), w, "l",
                           CFG)
    # ties aside: a token whose choice agrees has the reference's gates
    same = (np.asarray(idx) == index).all(axis=1)
    assert same.mean() > 0.99
    np.testing.assert_allclose(gate[same], np.asarray(g)[same], rtol=1e-5)
    np.testing.assert_allclose(gate.sum(axis=1), 1.0, rtol=1e-5)
    with pytest.raises(ValueError, match="score_func"):
        _expert_layer_program([(0, 2)], score_func="tanh")


def test_the_shares_add_up_to_the_uncut_layer():
    """8 experts in four shares of 2 (as the cell's 64 in eight of 8): the
    parts the shares give sum to the uncut reference's whole expert layer;
    there is no shared expert to count once."""
    shares = [(0, 2), (2, 2), (4, 2), (6, 2)]
    main, startup, _, parts, rows = _expert_layer_program(shares)
    x = np.random.default_rng(4).normal(
        size=(B, T, CFG["hidden_size"])).astype("float32")
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        out = exe.run(main, feed={"x": x}, fetch_list=parts + rows)
        w = _uncut_weights(fluid.global_scope(), shares)
    with jax.default_matmul_precision("highest"):
        want = REF.expert_layer(jnp.asarray(x), w, "l", CFG, first=0)
    np.testing.assert_allclose(sum(out[:4]), want, atol=1e-5, rtol=1e-4)
    # every choice of every token landed on exactly one share
    assert sum(int(r.sum()) for r in out[4:]) == B * T * 2
    # and one share alone is not the layer
    assert np.abs(out[0] - np.asarray(want)).max() > 1e-2


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_a_softmax_step_counts_its_bias_from_each_experts_own_share(seed):
    """Experts whose logits spread differently (0.3 to 1.5) about means
    that lie apart, tokens in lumps of one id with a little of their own:
    from the level that top_k / E of the tokens score an expert above,
    every expert's load lies near the even share and so do the 8 held;
    from the experts' mean log-scores (the rule before) they do not.  The
    bias is minus that level, and the gates come from the scores alone."""
    from paddle_tpu.parallel.moe import (held_rows, share_level,
                                         sigmoid_topk_route)

    rng = np.random.default_rng(seed)
    e, k, t, held = 64, 8, 8192, 8
    p = 1.0 / np.arange(1, 513) ** 1.1
    ids = rng.choice(512, size=t, p=p / p.sum())
    z = jnp.asarray(rng.normal(0, 0.5, (e,)) + rng.uniform(0.3, 1.5, (e,)) * (
        rng.normal(0, 1, (512, e))[ids] + rng.normal(0, 0.5, (t, e))),
        jnp.float32)
    w, b = jnp.eye(e, dtype=jnp.float32), jnp.zeros(e, jnp.float32)
    idx, gates, used = sigmoid_topk_route(z, w, b, k, center=True,
                                          score_func="softmax")
    logp = jax.nn.log_softmax(z, axis=-1)
    level = share_level(logp, k / e)
    np.testing.assert_array_equal(used, -level)
    above = np.asarray((logp > level).sum(axis=0))
    assert above.max() <= t * k // e and above.min() >= t * k // e - 2
    picked = jnp.take_along_axis(jnp.exp(logp), idx, axis=-1)
    np.testing.assert_allclose(
        gates, picked / picked.sum(-1, keepdims=True), rtol=1e-5)

    def load(idx):
        given = np.bincount(np.asarray(idx).reshape(-1), minlength=e)
        return (given.max() / given.mean(),
                int(held_rows(idx, 0, held)[1].sum()) / (t * k * held / e))

    fullest, here = load(idx)
    _, by_mean = jax.lax.top_k(logp - logp.mean(axis=0), k)
    assert fullest < 1.4 and abs(here - 1) < 0.05
    assert load(by_mean)[0] > 1.5 * fullest


@pytest.mark.parametrize("choices,held,total,want", [
    (16384 * 8, 8, 64, 24576),      # the mellum2 cell: 1.5 x 16,384
    (16384 * 6, 16, 128, 18432),    # the kanana cell: 1.5 x 12,288
    (256, 2, 8, 1024),              # never under 1,024
    (16384 * 8, 8, None, 8192),     # the share not known: BLOCK_ROWS
    (1000, 3, 7, 1024),             # whole 1,024s, rounded up
    (10000, 3, 7, 7168),
])
def test_the_expert_loops_block_holds_one_and_a_half_even_shares(
        choices, held, total, want):
    from paddle_tpu.parallel import moe

    assert moe.block_rows(choices, held, total) == want


def test_the_block_size_changes_the_trips_and_not_the_result():
    """The same layer walked in blocks of 8,192 (share unknown: every
    routed row in one trip here) and of 2,048 (2 of 8 experts held, and
    given most of the choices: two trips): equal results and
    gradients."""
    from paddle_tpu.parallel import moe

    rng = np.random.default_rng(0)
    t, d, f, k, held = 1500, 16, 24, 2, 2
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    idx = jnp.asarray(np.where(rng.uniform(size=(t, k)) < 0.8,
                               rng.integers(2, 4, size=(t, k)),
                               rng.integers(0, 8, size=(t, k))), jnp.int32)
    gates = jnp.asarray(rng.uniform(size=(t, k)), jnp.float32)
    w = [jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
         for s in ((held, d, f), (held, d, f), (held, f, d))]

    def run(total):
        def loss(x, gates, *w):
            out, rows = moe.held_experts_ffn(x, idx, gates, *w, first=2,
                                             total=total)
            return jnp.sum(out ** 2), rows
        (value, rows), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(x, gates, *w)
        return value, rows, grads

    one, rows, g_one = run(None)
    many, rows_again, g_many = run(8)
    assert moe.block_rows(t * k, held, 8) == 2048 < int(rows.sum())
    assert (rows == rows_again).all()
    np.testing.assert_allclose(one, many, rtol=1e-5)
    for a, b in zip(g_one, g_many):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-4)
    assert int(moe.blocks_run(rows, 2048)) == 2
    assert int(moe.blocks_run(rows)) == 1
