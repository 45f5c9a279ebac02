"""The config-built decoder (``models/decoder.py``: latent attention, a
dropless top-k expert layer over the experts held here, shared experts)
against its plain reference ``chipbench/reference/kanana.py``, at a small
size on the CPU: forward, gradients, the share of a deployment, and the
pieces it is made of (the flash kernels at ``d_qk != d_v`` in interpret
mode, the AMP rewrite inside recompute regions, the device counters)."""

import importlib
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import paddle_tpu as fluid  # noqa: E402
from chipbench import manifest as mf  # noqa: E402
from paddle_tpu.models import decoder  # noqa: E402

REF = mf.load_by_name("reference", "kanana")
FA = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

# hidden 64, 4 heads, 8 experts top-2, 2 held, vocabulary 256
CFG = dict(decoder.DECODER_TINY)
T, B = 32, 2


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


@pytest.mark.parametrize("recompute", [True, False])
def test_forward_logits_and_loss_match_the_reference(recompute):
    cfg = dict(CFG, recompute=recompute)
    main, startup, loss = _build(train=False, cfg=cfg)
    head = [op for op in main.global_block().ops
            if op.type == "softmax_with_cross_entropy"][0]
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
    "decoder.layer1.attn.q.w", "decoder.layer1.attn.kvb.w",
    "decoder.layer1.moe.router.w", "decoder.layer2.moe.experts.1.gate",
    "decoder.layer1.moe.shared.up.w", "decoder.embed"])
def test_gradient_matches_jax_grad_of_the_reference(name):
    """One parameter of each kind, through ``append_backward`` and the
    recompute regions' own ``jax.vjp``."""
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


def test_the_held_expert_gets_no_gradient_from_rows_routed_elsewhere():
    """A held expert no token chose keeps a zero gradient: its rows are
    the only way to it."""
    main, startup, loss = _build(train=False, backward=True)
    names = ["decoder.layer1.moe.experts.%d.down@GRAD" % e for e in (0, 1)]
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        scope = fluid.global_scope()
        bias = np.zeros(CFG["n_routed_experts"], "float32")
        bias[1] = -10.0             # nobody chooses held expert 1
        scope.set("decoder.layer1.moe.router.b", jnp.asarray(bias))
        got = exe.run(main, feed=_feed(2), fetch_list=names)
    assert np.abs(got[0]).max() > 0 and np.abs(got[1]).max() == 0


def test_training_lowers_the_loss_and_counts_rows(monkeypatch):
    from paddle_tpu.observability import metrics, runtime

    main, startup, loss = _build(train=True, amp=True)
    feed = _feed(3)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        losses = [float(exe.run(main, feed=feed, fetch_list=[loss])[0][0])
                  for _ in range(6)]
        counted = runtime.publish_moe_counters()
        again = runtime.publish_moe_counters()
    assert losses[-1] < losses[0] - 0.3
    assert sorted(counted) == ["1", "2"] and counted == again
    tokens = B * T
    for layer, c in counted.items():
        assert c["steps"] == 6 and len(c["rows"]) == CFG["experts_held"]
        assert c["possible"] == 6 * tokens * CFG["num_experts_per_tok"]
        assert 0 < sum(c["rows"]) <= c["possible"]
        got = metrics.registry().get("moe_rows_routed_here_total",
                                     layer=layer)
        assert got is not None and got.value == sum(c["rows"])
        assert metrics.registry().get(
            "moe_expert_rows_max", layer=layer).value == max(c["rows"])
    spans = [r for r in fluid.observability.tracing.get_tracer().records()
             if r["name"] == "executor.compile"
             and r.get("attrs", {}).get("moe_layers")] \
        if hasattr(fluid, "observability") else []
    for r in spans:
        assert r["attrs"]["experts_held"] == 2
        assert r["attrs"]["experts_total"] == 8


# -- the correction bias a step makes for itself --------------------------------

@pytest.mark.parametrize("lead", [0.3, 0.8])
def test_centering_the_scores_evens_a_load_that_a_few_experts_lead(lead):
    """Tokens that differ little beside what all of them share (``lead``:
    how far the experts' mean logits lie apart; a token's own part 0.4):
    uncentred, the few experts with the highest means take most choices;
    chosen by how much more an expert scores a token than it scores the
    tokens on average, the load is near even, in all and in a share."""
    from paddle_tpu.parallel.moe import held_rows, sigmoid_topk_route

    rng = np.random.default_rng(0)
    e, k, t, held = 32, 4, 4096, 4
    x = jnp.asarray(rng.normal(0, lead, (e,)) + rng.normal(0, 0.4, (t, e)),
                    jnp.float32)
    w, b = jnp.eye(e, dtype=jnp.float32), jnp.zeros(e, jnp.float32)
    load = {}
    for center in (False, True):
        idx, gates, used = sigmoid_topk_route(x, w, b, k, center=center)
        given = np.bincount(np.asarray(idx).reshape(-1), minlength=e)
        load[center] = (given.max() / given.mean(),
                        int(held_rows(idx, 0, held)[1].sum()))
        s = jax.nn.sigmoid(x)
        want = -s.mean(0) if center else b
        np.testing.assert_allclose(used, want, rtol=1e-6, atol=1e-7)
        picked = jnp.take_along_axis(s, idx, axis=-1)
        np.testing.assert_allclose(
            gates, picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    even = t * k * held / e
    assert load[False][0] > 1.5 * load[True][0]
    assert load[True][0] < 1.4 and abs(load[True][1] - even) < 0.15 * even


def test_a_step_keeps_the_bias_it_made_for_test_mode():
    """With ``router_bias_from_batch`` a training step routes by minus
    the experts' mean scores and leaves that in the router's ``b``; the
    test-mode program routes by what it finds there, as the reference
    does, so the two agree on the weights a step left."""
    main, startup, loss = _build(train=True)
    test_main, _, test_loss = _build(train=False)
    head = [op for op in test_main.global_block().ops
            if op.type == "softmax_with_cross_entropy"][0]
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        before = _weights()
        exe.run(main, feed=_feed(3), fetch_list=[loss])
        after = _weights()
        logits, got = exe.run(test_main, feed=_feed(4),
                              fetch_list=[head.input("Logits")[0],
                                          test_loss])
    for layer in (1, 2):
        name = "decoder.layer%d.moe.router.b" % layer
        assert not np.array_equal(before[name], after[name])
        # minus a mean of sigmoids of small logits
        assert np.all((after[name] < -0.4) & (after[name] > -0.6))
    want = _reference(after, _feed(4))
    np.testing.assert_allclose(logits, want["logits"], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(got[0], want["loss"], rtol=1e-5)


# -- the share and the model ---------------------------------------------------

def _expert_layer_program(shares, count=False):
    """One expert layer over [B, T, 64] with the held experts of each of
    ``shares`` ((first, held) pairs) as ``moe_experts`` ops of their own,
    one router and the shared experts once.  Returns what to fetch: each
    share's routed part, the shared part, each share's rows.  ``count``:
    each share keeps device counters under the layer label of its first
    expert."""
    cfg = CFG
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[T, cfg["hidden_size"]],
                              dtype="float32")
        init = fluid.initializer.Normal(0.0, 0.3)
        index, gate = fluid.layers.moe_route(
            x, cfg["n_routed_experts"], cfg["num_experts_per_tok"],
            scale=cfg["routed_scaling_factor"],
            param_attr=fluid.ParamAttr(name="l.router.w", initializer=init),
            bias_attr=fluid.ParamAttr(
                name="l.router.b",
                initializer=fluid.initializer.Normal(0.0, 0.05)))
        parts, rows = [], []
        for first, held in shares:
            y, r = fluid.layers.moe_experts(
                x, index, gate, cfg["moe_intermediate_size"], held,
                first_expert=first, param_attr=fluid.ParamAttr(
                    name="l.share%d" % first, initializer=init))
            parts.append(y)
            rows.append(r)
            if count:
                fluid.layers.moe_count_rows(r, index, layer=first)
        shared = decoder._swiglu_mlp(
            x, 2 * cfg["moe_intermediate_size"], "l.shared",
            dict(cfg, initializer_range=0.3))
    return main, startup, parts, shared, rows


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


def test_the_shares_add_up_to_the_uncut_layer():
    """8 experts in four shares of 2: the shares' routed parts, plus the
    shared experts counted once, are the uncut reference's whole layer."""
    shares = [(0, 2), (2, 2), (4, 2), (6, 2)]
    main, startup, parts, shared, rows = _expert_layer_program(shares)
    x = np.random.default_rng(4).normal(
        size=(B, T, CFG["hidden_size"])).astype("float32")
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        out = exe.run(main, feed={"x": x},
                      fetch_list=parts + [shared] + rows)
        w = _uncut_weights(fluid.global_scope(), shares)
    with jax.default_matmul_precision("highest"):
        want = REF.expert_layer(jnp.asarray(x), w, "l", CFG, first=0)
    got = sum(out[:4]) + out[4]
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-4)
    # every choice of every token landed on exactly one share
    assert sum(int(r.sum()) for r in out[5:]) == B * T * 2
    # and one share alone is not the layer
    assert np.abs(out[0] + out[4] - np.asarray(want)).max() > 1e-2


def test_nothing_is_dropped_when_every_choice_lands_here():
    """The correction bias sends every token's whole choice to the two
    held experts: rows = tokens * top_k, an imbalance a capacity limit
    would cut, and the result is still the reference's."""
    shares = [(4, 2)]
    main, startup, parts, shared, rows = _expert_layer_program(shares)
    x = np.random.default_rng(5).normal(
        size=(B, T, CFG["hidden_size"])).astype("float32")
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        scope = fluid.global_scope()
        bias = np.full(8, -5.0, "float32")
        bias[4:6] = 5.0
        scope.set("l.router.b", jnp.asarray(bias))
        routed, r = exe.run(main, feed={"x": x},
                            fetch_list=[parts[0], rows[0]])
        w = _uncut_weights(scope, shares)
    assert r.tolist() == [B * T, B * T]
    with jax.default_matmul_precision("highest"):
        want = REF.routed_part(jnp.asarray(x), w, "l", CFG, first=4)
    # float32 sums whose terms reach 20: one element in 4096 at 2e-5
    np.testing.assert_allclose(routed, want, atol=1e-4, rtol=1e-4)


def test_what_the_grouped_kernel_leaves_unwritten_reaches_nothing(
        monkeypatch):
    """On the chip ``ragged_dot`` writes only the rows its counts cover
    (and, to the weights, only the groups that have rows); the rest is
    whatever the buffer held.  Here the rest is made NaN, forward and
    backward, with a held expert that no token chose: the layer's result
    and every gradient stay finite and equal to the reference's."""
    from paddle_tpu.parallel import moe

    real = jax.lax.ragged_dot

    @jax.custom_vjp
    def poisoned(lhs, rhs, rows):
        covered = (jnp.arange(lhs.shape[0]) < rows.sum())[:, None]
        return jnp.where(covered, real(lhs, rhs, rows), jnp.nan)

    def fwd(lhs, rhs, rows):
        return poisoned(lhs, rhs, rows), (lhs, rhs, rows)

    def bwd(res, ct):
        lhs, rhs, rows = res
        covered = (jnp.arange(lhs.shape[0]) < rows.sum())[:, None]
        # the kernels read only the rows the counts cover
        _, pullback = jax.vjp(lambda l, r: real(l, r, rows),
                              jnp.where(covered, lhs, 0), rhs)
        d_lhs, d_rhs = pullback(jnp.where(covered, ct, 0))
        return (jnp.where(covered, d_lhs, jnp.nan),
                jnp.where((rows > 0)[:, None, None], d_rhs, jnp.nan), None)

    poisoned.defvjp(fwd, bwd)
    monkeypatch.setattr(jax.lax, "ragged_dot", poisoned)
    rng = np.random.default_rng(0)
    t, d, f, e, k, held, first = 64, 16, 24, 8, 2, 3, 2
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    w = {"l.router.w": jnp.asarray(rng.normal(size=(d, e)), jnp.float32),
         "l.router.b": jnp.zeros(e).at[3].set(-50.0)}   # nobody chooses 3
    for e in range(held):
        for name, shape in (("gate", (d, f)), ("up", (d, f)),
                            ("down", (f, d))):
            w["l.experts.%d.%s" % (e, name)] = jnp.asarray(
                0.2 * rng.normal(size=shape), jnp.float32)
    cfg = dict(CFG, num_experts_per_tok=k)

    def program(x, w):
        idx, gates, _ = moe.sigmoid_topk_route(
            x, w["l.router.w"], w["l.router.b"], k,
            cfg["routed_scaling_factor"])
        stacked = [jnp.stack([w["l.experts.%d.%s" % (e, name)]
                              for e in range(held)])
                   for name in ("gate", "up", "down")]
        out, rows = moe.held_experts_ffn(x, idx, gates, *stacked,
                                         first=first)
        return out, rows

    out, rows = program(x, w)
    assert rows[1] == 0 and rows.sum() < t * k
    got = jax.grad(lambda x, w: jnp.sum(program(x, w)[0] ** 2),
                   argnums=(0, 1))(x, w)
    with jax.default_matmul_precision("highest"):
        monkeypatch.setattr(jax.lax, "ragged_dot", real)
        want_out = REF.routed_part(x, w, "l", cfg, first)
        want = jax.grad(lambda x, w: jnp.sum(REF.routed_part(
            x, w, "l", cfg, first) ** 2), argnums=(0, 1))(x, w)
    np.testing.assert_allclose(out, want_out, atol=1e-5, rtol=1e-4)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-3)


# -- the loop over blocks of the choices' order ---------------------------------

def _steered_layer(here, empty_middle=False):
    """A layer of 8 experts, top 2, experts 2..4 held, over ``T = B``
    tokens (B the loop's block: 2 B choices, two blocks) whose first
    features tell the router which two experts a token takes, so that
    exactly ``here`` choices name a held expert, spread over the tokens
    as evenly as whole choices allow.  ``empty_middle``: held expert 3
    gets none."""
    from paddle_tpu.parallel import moe

    rng = np.random.default_rng(here)
    e, k, held, first = 8, 2, 3, 2
    t, d, f = moe.BLOCK_ROWS, 16, 24
    theirs, elsewhere = [2, 4] if empty_middle else [2, 3, 4], [0, 1, 5, 6, 7]
    pick = np.empty((t, k), int)
    for i in range(t):
        n = here // t + (i < here % t)      # held experts token i takes
        pick[i] = np.concatenate([
            rng.choice(theirs, size=n, replace=False),
            rng.choice(elsewhere, size=k - n, replace=False)])
    pick = pick[rng.permutation(t)]
    x = rng.normal(size=(t, d))
    x[:, :e] = -1.0 + 0.1 * rng.normal(size=(t, e))
    np.put_along_axis(x, pick, 1.0 + 0.1 * rng.normal(size=(t, k)), axis=1)
    w = {"l.router.w": jnp.asarray(
            np.eye(d, e) * 2 + 0.02 * rng.normal(size=(d, e)), jnp.float32),
         "l.router.b": jnp.zeros(e, jnp.float32)}
    for i in range(held):
        for name, shape in (("gate", (d, f)), ("up", (d, f)),
                            ("down", (f, d))):
            w["l.experts.%d.%s" % (i, name)] = jnp.asarray(
                0.2 * rng.normal(size=shape), jnp.float32)
    cfg = dict(CFG, num_experts_per_tok=k)

    def program(x, w):
        idx, gates, _ = moe.sigmoid_topk_route(
            x, w["l.router.w"], w["l.router.b"], k,
            cfg["routed_scaling_factor"])
        stacked = [jnp.stack([w["l.experts.%d.%s" % (i, name)]
                              for i in range(held)])
                   for name in ("gate", "up", "down")]
        return moe.held_experts_ffn(x, idx, gates, *stacked, first=first)

    def reference(x, w):
        return REF.routed_part(x, w, "l", cfg, first)

    return jnp.asarray(x, jnp.float32), w, program, reference


@pytest.mark.parametrize("load", [
    "no_row", "one_row", "one_block", "one_block_and_a_row",
    "an_empty_expert_in_the_middle", "every_choice"])
def test_the_loop_over_blocks_matches_the_reference_at_any_load(load):
    """Values and the gradients to x, the router's weight and the three
    expert weights against ``REF.routed_part``, with the routed rows
    ending before, on and after the edges of the loop's blocks."""
    from paddle_tpu.parallel import moe

    b = moe.BLOCK_ROWS
    here = {"no_row": 0, "one_row": 1, "one_block": b,
            "one_block_and_a_row": b + 1,
            "an_empty_expert_in_the_middle": b // 2 + 3,
            "every_choice": 2 * b}[load]
    x, w, program, reference = _steered_layer(
        here, empty_middle=load == "an_empty_expert_in_the_middle")
    out, rows = jax.jit(program)(x, w)
    assert int(rows.sum()) == here
    assert int(moe.blocks_run(rows)) == -(-here // b)
    if load == "an_empty_expert_in_the_middle":
        assert rows[1] == 0 and rows[0] > 0 and rows[2] > 0
    got = jax.jit(jax.grad(lambda x, w: jnp.sum(program(x, w)[0] ** 2),
                           argnums=(0, 1)))(x, w)
    with jax.default_matmul_precision("highest"):
        want_out = reference(x, w)
        want = jax.grad(lambda x, w: jnp.sum(reference(x, w) ** 2),
                        argnums=(0, 1))(x, w)
    np.testing.assert_allclose(out, want_out, atol=1e-5, rtol=1e-4)
    assert (np.abs(np.asarray(want_out)).max() > 0) == (here > 0)
    for (path, a), c in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        scale = np.abs(np.asarray(c)).max()
        np.testing.assert_allclose(a, c, atol=1e-6 + 1e-4 * scale,
                                   rtol=1e-3, err_msg=str(path))
    assert np.abs(np.asarray(got[1]["l.router.w"])).max() > 0 or here == 0


def test_the_counters_tell_the_rows_the_loop_moved():
    """``moved`` is a whole block for each block the loop ran, summed
    over the steps, beside ``rows``, ``possible`` and ``steps`` as the
    reference's router gives them for the same feeds; with every choice
    routed here the loop runs every block and ``moved == possible``."""
    from paddle_tpu.observability import metrics, runtime
    from paddle_tpu.parallel import moe

    block, k, first, held = moe.BLOCK_ROWS, 2, 4, 2
    main, startup, parts, _, rows = _expert_layer_program(
        [(first, held)], count=True)
    rng = np.random.default_rng(6)
    feeds = [rng.normal(size=(block // T, T, CFG["hidden_size"])).astype(
        "float32") for _ in range(3)]           # `block` tokens a step
    here = np.full(8, -5.0, "float32")
    here[first:first + held] = 5.0
    want = {"rows": np.zeros(held, int), "possible": 0, "moved": 0,
            "steps": 0}
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        scope = fluid.global_scope()
        for step, x in enumerate(feeds):
            if step == 2:           # the last step: every choice here
                scope.set("l.router.b", jnp.asarray(here))
            exe.run(main, feed={"x": x}, fetch_list=[parts[0]])
            w = {n: np.asarray(scope.get(n)) for n in ("l.router.w",
                                                       "l.router.b")}
            with jax.default_matmul_precision("highest"):
                idx, _ = REF.route(jnp.asarray(x), w, "l", CFG)
            given = np.bincount(np.asarray(idx).reshape(-1), minlength=8)[
                first:first + held]
            want["rows"] += given
            want["possible"] += block * k
            want["moved"] += block * -(-int(given.sum()) // block)
            want["steps"] += 1
            if step == 1:
                before = runtime.publish_moe_counters()[str(first)]
        counted = runtime.publish_moe_counters()
        assert counted == runtime.publish_moe_counters()
    got = counted[str(first)]
    want["rows"] = want["rows"].tolist()
    assert got == want
    # a quarter of the choices name a held expert: one block of two
    assert before["moved"] == 2 * block and before["possible"] == 4 * block
    assert got["moved"] - before["moved"] == 2 * block \
        == got["possible"] - before["possible"]
    for name, key in (("moe_buffer_rows_moved_total", "moved"),
                      ("moe_rows_possible_total", "possible")):
        assert metrics.registry().get(
            name, layer=str(first)).value == got[key]


def test_no_part_of_the_layer_has_a_row_for_every_choice():
    """The lowered layer and its gradient hold nothing of ``T * K`` rows
    of the model's or the experts' width (a gather, a scatter, a select
    or a product over the whole order of choices), only blocks; and the
    compiler cannot tell how often the loop runs."""
    import re

    from paddle_tpu.parallel import moe

    block = moe.BLOCK_ROWS
    t, k, d, f, held = 2 * block, 3, 16, 24, 4
    args = [jax.ShapeDtypeStruct(s, dt) for s, dt in (
        ((t, d), jnp.float32), ((t, k), jnp.int32), ((t, k), jnp.float32),
        ((held, d, f), jnp.float32), ((held, d, f), jnp.float32),
        ((held, f, d), jnp.float32))]

    def layer(x, idx, gates, *w):
        return moe.held_experts_ffn(x, idx, gates, *w)[0]

    def grad(x, idx, gates, *w):
        return jax.grad(lambda x, gates, *w: jnp.sum(
            layer(x, idx, gates, *w) ** 2), argnums=(0, 1, 2, 3, 4))(
                x, gates, *w)

    for fn in (layer, grad):
        lowered = jax.jit(fn).lower(*args)
        shapes = set(re.findall(r"tensor<(\d+)x(\d+)x[a-z]",
                                lowered.as_text()))
        assert {(str(block), str(d)), (str(block), str(f))} <= shapes
        assert not {(str(t * k), str(d)), (str(t * k), str(f))} & shapes
        compiled = lowered.compile().as_text()
        assert re.search(r" while\(", compiled)
        assert "known_trip_count" not in compiled


# -- the pieces ------------------------------------------------------------------

@pytest.mark.parametrize("blocks", [None, (64, 128)])
def test_flash_kernels_at_d_qk_other_than_d_v(blocks, monkeypatch):
    """Interpret mode, causal, forward and backward against
    ``mha_reference``: Q and K 48 wide, V and the output 32."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
    if blocks:
        monkeypatch.setattr(FA, "_pick_blocks", lambda tq, tk: blocks)
    rng = np.random.RandomState(0)
    b, h, t, dqk, dv = 2, 2, 256, 48, 32
    q, k = (jnp.asarray(rng.randn(b, h, t, dqk).astype("float32"))
            for _ in range(2))
    v = jnp.asarray(rng.randn(b, h, t, dv).astype("float32"))
    assert FA.routes_to_kernel(q, k, None, v)
    got = FA.flash_attention(q, k, v, causal=True)
    want = FA.mha_reference(q, k, v, causal=True)
    assert got.shape == (b, h, t, dv)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    w = jnp.asarray(rng.randn(b, h, t, dv).astype("float32"))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=True) * w)

    g1 = jax.grad(loss(FA.flash_attention), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(FA.mha_reference), argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        assert a.shape == b_.shape
        np.testing.assert_allclose(a, b_, atol=5e-4, rtol=1e-3)


def test_rotary_embedding_rotates_part_of_a_head():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[2, 16, 12], dtype="float32")
        out = fluid.layers.rotary_embedding(x, rotary_dim=8, offset=4,
                                            theta=100.0)
        half = fluid.layers.rotary_embedding(x, rotary_dim=8, offset=4,
                                             theta=100.0, interleaved=False)
    xv = np.random.default_rng(0).normal(size=(3, 2, 16, 12)).astype("f4")
    exe = fluid.Executor(fluid.CPUPlace())
    got, got_half = exe.run(main, feed={"x": xv}, fetch_list=[out, half])
    np.testing.assert_array_equal(got[..., :4], xv[..., :4])
    want = np.asarray(REF._rotary(
        jnp.asarray(xv[..., 4:].transpose(0, 2, 1, 3)), 100.0)
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(got[..., 4:], want, atol=1e-6)
    np.testing.assert_array_equal(got[:, :, 0], xv[:, :, 0])   # position 0
    # the two pairings are one rotation under a permutation of features
    perm = np.concatenate([np.arange(0, 8, 2), np.arange(1, 8, 2)])
    xp = xv.copy()
    xp[..., 4:] = xv[..., 4:][..., perm]
    got_p = exe.run(main, feed={"x": xp}, fetch_list=[half])[0]
    np.testing.assert_allclose(got_p[..., 4:], got[..., 4:][..., perm],
                               atol=1e-6)
    assert not np.allclose(got_half, got)


def test_amp_reaches_into_recompute_regions_and_keeps_float32_slots():
    main, _, _ = _build(train=True, amp=True)
    region = main.block(1)
    types = [op.type for op in region.ops]
    assert "cast" in types and "moe_route" not in types    # layer 0: dense
    region = main.block(2)
    by_type = {}
    for op in region.ops:
        by_type.setdefault(op.type, []).append(op)

    def dtype_of(name):
        return region._find_var_recursive(name).dtype

    route = by_type["moe_route"][0]
    assert dtype_of(route.input("X")[0]) == "float32"      # cast back up
    assert dtype_of(route.input("Weight")[0]) == "float32"
    experts = by_type["moe_experts"][0]
    assert dtype_of(experts.input("Gate")[0]) == "float32"
    assert dtype_of(experts.input("WGate")[0]) == "bfloat16"
    assert dtype_of(experts.input("X")[0]) == "bfloat16"
    for op in by_type["rms_norm"]:
        assert dtype_of(op.input("Scale")[0]) == "float32"
    attn = by_type["fused_multihead_attention"][0]
    assert {dtype_of(attn.input(s)[0]) for s in "QKV"} == {"bfloat16"}


def test_device_tags_in_the_hlo():
    """The scope paths the benchmark's trace reduction reads: the Program
    op (or its ``device_tag``) is the innermost ``pd<i>_<tag>`` scope, and
    a part of an op is named inside the op's own scope, with its index.
    (Where jax differentiates a recompute region it rewrites each scope
    to ``transpose(jvp(pd..))``: matching that is the reader's to do.)"""
    import re

    from chipbench import xplane

    main, startup, loss = _build(train=False, backward=True)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        # a gradient among the fetches, or the backward is dead code
        exe.run(main, feed=_feed(), fetch_list=[loss, "decoder.embed@GRAD"])
        from paddle_tpu import executor

        block = executor._LAST_COMPILED_BLOCK
        scope = fluid.global_scope()
        feed = {n: jnp.asarray(v) for n, v in _feed().items()}
        text = block.jitted.lower(
            feed, {n: scope.get(n) for n in block.rw_names},
            {n: scope.get(n) for n in block.ro_names},
            executor.rng_key(0)).compile().as_text()
    paths = set(xplane.op_names_in(text).values())
    tags = {xplane.program_op(p) for p in paths}
    assert {"mla_attention", "dense_mlp", "moe_shared", "lm_head",
            "moe_route", "moe_experts.dispatch", "moe_experts.products",
            "moe_experts.combine"} <= tags
    parts = [re.search(r"pd(\d+)_moe_experts/pd(\d+)_moe_experts\.", p)
             for p in paths]
    assert any(parts) and all(m.group(1) == m.group(2) for m in parts if m)
