"""The Nemotron-H decoder (``models/decoder.py`` with a
``hybrid_override_pattern``: layers of one part each, Mamba-2 mixers with
groups of B and C, relu² experts with no gate beside a shared one, NoPE
grouped-query attention, an untied head) against its plain reference
``chipbench/reference/nemotron.py`` at a small size on the CPU, built
through ``chipbench/builders/nemotron.py``; and the pieces it forced: the
SSD scan kernels with B and C per group (interpret mode) and their XLA
twin against the recurrence as written, the grouped gated norm, the relu²
expert layer (kept products across a recompute region), and the share of
the expert layer against the uncut layer."""

import collections
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
from chipbench.selftest.test_nemotron import NEMOTRON_TINY  # noqa: E402
from paddle_tpu.models import decoder  # noqa: E402
from paddle_tpu.ops.pallas import ssd_scan as S  # noqa: E402
from paddle_tpu.parallel import moe  # noqa: E402

REF = mf.load_by_name("reference", "nemotron")
BUILDER = mf.load_by_name("builders", "nemotron")

# hidden 64; "MEM*E": two Mamba-2 layers of 4 heads of 32 in 2 groups of
# B and C (state 16), two relu² expert layers (2 of 8 experts held, top 2,
# a shared expert of 48), NoPE attention 4/2 of 16; chunks of 16 over 48
# positions: the state crosses two
CONFIG = NEMOTRON_TINY
T, B = 48, 2


def _scan_inputs(seed, bt, t, h, p, g, n):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((bt, t, h, p))
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (bt, t, h)))
    a = -rng.uniform(1, 16, h)
    b, c = rng.standard_normal((2, bt, t, g, n))
    d = rng.standard_normal(h)
    return [jnp.asarray(v, jnp.float32) for v in (x, dt, a, b, c, d)]


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


# -- the scan with groups against the recurrence ------------------------------

@pytest.mark.parametrize("path,t,h,p,g,chunk,block,blocks_a_group", [
    ("interpret", 256, 16, 64, 2, 128, 16, 1),   # a head block a group
    ("interpret", 256, 32, 64, 2, 128, 8, 2),    # two head blocks a group
    ("interpret", 300, 32, 32, 4, 128, 8, 1),    # a padded tail
    ("interpret", 256, 16, 64, 1, 128, 16, None),  # one group, as granite
    ("off", 100, 6, 8, 3, 16, 16, None),         # XLA's chunks, any widths
])
def test_grouped_scan_matches_the_recurrence_in_value_and_gradient(
        path, t, h, p, g, chunk, block, blocks_a_group, monkeypatch):
    """Forward and the gradients of x, Δ, A, B, C and D against the
    reference's recurrence, head h reading group ``h // (H / G)``; B and
    C [Bt, T, G, N], or [Bt, T, N] for one group.  A head block lies
    inside one group, and dB, dC sum the group's blocks alone."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS", path)
    monkeypatch.setattr(S, "HEADS_A_BLOCK", block)
    args = _scan_inputs(0, 2, t, h, p, g, 16)
    grouped = list(args)
    if g == 1:
        args[3], args[4] = args[3][:, :, 0], args[4][:, :, 0]
    assert S.routes_to_kernel(args[0], args[3], chunk) == (
        path == "interpret")
    if path == "interpret":
        hb = S._heads_a_block(h, p, g)
        assert hb <= h // g and (h // g) % hb == 0
        per = S._grouped(args[3], args[4], h * p, hb, p)[2]
        assert per == blocks_a_group
    w = jnp.asarray(np.random.default_rng(1).standard_normal(
        (2, t, h, p)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        y = S.ssd_scan(*args, chunk=chunk)
        assert _rel(y, REF.scan(*grouped)) < 1e-5
        grads = jax.grad(lambda *a: jnp.sum(S.ssd_scan(*a, chunk=chunk)
                                            * w), argnums=range(6))(*args)
        wants = jax.grad(lambda *a: jnp.sum(REF.scan(*a) * w),
                         argnums=range(6))(*grouped)
    for name, got, ref in zip("x dt a b c d".split(), grads, wants):
        assert _rel(np.reshape(got, ref.shape), ref) < 1e-4, name


@pytest.mark.parametrize("g", [1, 2])
def test_the_kernels_and_xlas_chunks_agree_with_groups(g, monkeypatch):
    """The Pallas kernels (interpret mode) against ``_chunked_xla`` at
    the same chunks, forward and backward: one algorithm, two lowerings."""
    args = _scan_inputs(2, 1, 256, 16, 64, g, 16)
    if g == 1:
        args[3], args[4] = args[3][:, :, 0], args[4][:, :, 0]
    w = jnp.asarray(np.random.default_rng(3).standard_normal(
        (1, 256, 16, 64)), jnp.float32)
    out = {}
    for path in ("interpret", "off"):
        monkeypatch.setenv("PADDLE_TPU_PALLAS", path)
        assert S.routes_to_kernel(args[0], args[3], 128) == (
            path == "interpret")
        with jax.default_matmul_precision("highest"):
            out[path] = jax.value_and_grad(
                lambda *a: jnp.sum(S.ssd_scan(*a, chunk=128) * w),
                argnums=range(6))(*args)
    (v1, g1), (v2, g2) = out["interpret"], out["off"]
    assert abs(float(v1) - float(v2)) < 1e-4 * abs(float(v2))
    for name, a, b in zip("x dt a b c d".split(), g1, g2):
        assert _rel(a, b) < 1e-5, name


def test_a_group_reads_its_own_b_and_c(monkeypatch):
    """Moving one group's B changes the heads of that group alone."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
    x, dt, a, b, c, d = _scan_inputs(4, 1, 256, 32, 64, 4, 16)
    before = S.ssd_scan(x, dt, a, b, c, d, chunk=128)
    after = S.ssd_scan(x, dt, a, b.at[:, :, 2].multiply(-1.0), c, d,
                       chunk=128)
    moved = np.abs(np.asarray(after - before)).max(axis=(0, 1, 3))
    assert (moved[16:24] > 1e-3).all()
    assert (moved[:16] == 0).all() and (moved[24:] == 0).all()


def test_a_group_that_a_head_block_cannot_fill_runs_xlas_chunks(
        monkeypatch):
    """4 heads of 32 in 2 groups: a group's 64 lanes are no whole tile,
    so no head block lies inside a group and the XLA twin runs."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
    x, dt, a, b, c, d = _scan_inputs(5, 1, 128, 4, 32, 2, 16)
    assert S._heads_a_block(4, 32, 2) is None
    assert not S.routes_to_kernel(x, b, 128)
    assert S._heads_a_block(4, 32, 1) == 4
    with jax.default_matmul_precision("highest"):
        y = S.ssd_scan(x, dt, a, b, c, d, chunk=128)
        assert _rel(y, REF.scan(x, dt, a, b, c, d)) < 1e-5


def test_one_group_lowers_the_kernels_it_lowered_before(monkeypatch):
    """At G = 1 the kernels' block specs are the one-group ones: B and C
    [Bt, T, N] in, the same grid; given as [Bt, T, 1, N] they are the same
    numbers through the grouped specs."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
    x, dt, a, b, c, d = _scan_inputs(6, 1, 256, 16, 64, 1, 16)
    flat = jax.make_jaxpr(lambda *v: S.ssd_scan(*v, chunk=128))(
        x, dt, a, b[:, :, 0], c[:, :, 0], d)
    assert "name=ssd_scan_fwd" in str(flat)
    assert S._grouped(b[:, :, 0], c[:, :, 0], 1024, 16, 64)[2] is None
    one = S.ssd_scan(x, dt, a, b[:, :, 0], c[:, :, 0], d, chunk=128)
    grouped = S.ssd_scan(x, dt, a, b, c, d, chunk=128)
    np.testing.assert_allclose(one, grouped, rtol=1e-6, atol=1e-6)


# -- the grouped gated norm and relu² -----------------------------------------

def _run(build, feed):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        out = build()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        got = exe.run(main, feed=feed, fetch_list=[out])[0]
    return got, main


def test_rms_norm_gated_normalises_each_group():
    rng = np.random.default_rng(7)
    x, z = rng.standard_normal((2, 2, 5, 12)).astype("float32")

    def build():
        xv = fluid.layers.data("x", shape=[5, 12], dtype="float32")
        zv = fluid.layers.data("z", shape=[5, 12], dtype="float32")
        return fluid.layers.rms_norm_gated(xv, zv, epsilon=1e-5,
                                           group_size=4)

    got, main = _run(build, {"x": x, "z": z})
    g = (x * z / (1 + np.exp(-z))).reshape(2, 5, 3, 4)
    want = g / np.sqrt(np.mean(g * g, -1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(got, want.reshape(2, 5, 12), rtol=1e-5,
                               atol=1e-6)
    (op,) = [o for o in main.global_block().ops
             if o.type == "rms_norm_gated"]
    assert op.attrs["group_size"] == 4
    # over the whole width the groups' means are not the norm's
    whole = g.reshape(2, 5, 12) / np.sqrt(np.mean(
        g.reshape(2, 5, 12) ** 2, -1, keepdims=True) + 1e-5)
    assert np.abs(got - whole).max() > 1e-2


def test_relu2_is_the_square_of_relu():
    x = np.linspace(-2, 2, 24).reshape(2, 12).astype("float32")

    def build():
        return fluid.layers.relu2(
            fluid.layers.data("x", shape=[12], dtype="float32"))

    got, _ = _run(build, {"x": x})
    np.testing.assert_allclose(got, np.maximum(x, 0) ** 2, rtol=1e-6)


def _expert_inputs(seed, t=64, d=16, f=24, held=3, total=6, k=2):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    w_r = jnp.asarray(rng.standard_normal((d, total)) * 0.3, jnp.float32)
    idx, gates, _ = moe.sigmoid_topk_route(
        x, w_r, jnp.zeros(total), k, 2.5, True)
    up = jnp.asarray(rng.standard_normal((held, d, f)) * 0.3, jnp.float32)
    down = jnp.asarray(rng.standard_normal((held, f, d)) * 0.3, jnp.float32)
    return x, idx, gates, up, down


def _dense_relu2(x, idx, gates, up, down, first):
    out = jnp.zeros_like(x)
    for e in range(up.shape[0]):
        g = jnp.sum(jnp.where(idx == first + e, gates, 0.0), axis=-1)
        out = out + g[:, None] * (jnp.square(jax.nn.relu(x @ up[e]))
                                  @ down[e])
    return out


def test_relu2_experts_match_the_dense_sum_in_value_and_gradient():
    """``held_experts_ffn(activation="relu2")``: no gate weights, up and
    down alone, against every held expert over every token times its gate;
    the gradients of x, the gates, up and down."""
    x, idx, gates, up, down = _expert_inputs(8)
    with jax.default_matmul_precision("highest"):
        def ours(x, gates, up, down):
            return moe.held_experts_ffn(x, idx, gates, None, up, down,
                                        first=1, total=6,
                                        activation="relu2")[0]

        got = ours(x, gates, up, down)
        want = _dense_relu2(x, idx, gates, up, down, 1)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        w = jnp.asarray(np.random.default_rng(9).standard_normal(x.shape))
        g1 = jax.grad(lambda *a: jnp.sum(ours(*a) * w), argnums=range(4))(
            x, gates, up, down)
        g2 = jax.grad(lambda x, g, u, d: jnp.sum(
            _dense_relu2(x, idx, g, u, d, 1) * w), argnums=range(4))(
            x, gates, up, down)
    for name, a, b in zip(("x", "gates", "up", "down"), g1, g2):
        assert _rel(a, b) < 1e-5, name


def test_relu2_kept_products_give_the_same_gradients():
    """What a recompute region keeps (the choices and the first block's up
    product alone) handed to the re-run: the same value and gradients as
    computing everything anew, and the kept product is the up product."""
    x, idx, gates, up, down = _expert_inputs(10)
    (out, rows), kept = moe.held_experts_ffn(
        x, idx, gates, None, up, down, total=6, activation="relu2",
        return_residuals=True)
    assert len(kept) == 2 and kept[1].shape[1] == up.shape[2]

    def loss(x, gates, up, down, residuals=None):
        return jnp.sum(moe.held_experts_ffn(
            x, idx, gates, None, up, down, total=6, activation="relu2",
            residuals=residuals)[0] ** 2)

    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda *a: loss(*a, residuals=kept),
                       argnums=range(4))(x, gates, up, down)
        want = jax.grad(loss, argnums=range(4))(x, gates, up, down)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_the_swiglu_layer_is_built_as_before():
    """``moe_experts`` with no activation: gate, up and down weights and
    no ``activation`` attr, the op its SwiGLU cells lowered before."""
    def build(activation):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            x = fluid.layers.data("x", shape=[8, 16], dtype="float32")
            idx, gate = fluid.layers.moe_route(x, 4, 2)
            kw = {} if activation is None else {"activation": activation}
            fluid.layers.moe_experts(x, idx, gate, 24, 2, **kw)
        (op,) = [o for o in main.global_block().ops
                 if o.type == "moe_experts"]
        return op

    swiglu = build(None)
    assert [len(swiglu.input(s)) for s in ("WGate", "WUp", "WDown")] == \
        [2, 2, 2]
    assert "activation" not in swiglu.attrs
    relu2 = build("relu2")
    assert not relu2.input("WGate") and len(relu2.input("WUp")) == 2
    assert relu2.attrs["activation"] == "relu2"
    with pytest.raises(ValueError):
        build("gelu")


# -- the share against the uncut layer ----------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """8 relu² experts in four shares of 2: the program's routed parts
    (``held_experts_ffn`` from each share's first expert), plus the shared
    expert counted once, are the uncut reference's whole expert layer."""
    rng = np.random.default_rng(11)
    d, f, fs, total = 16, 24, 32, 8
    x = jnp.asarray(rng.standard_normal((2, 40, d)), jnp.float32)
    w = {"l.router.w": rng.standard_normal((d, total)) * 0.3,
         "l.router.b": rng.standard_normal(total) * 0.01,
         "l.shared.up.w": rng.standard_normal((d, fs)) * 0.3,
         "l.shared.down.w": rng.standard_normal((fs, d)) * 0.3}
    for e in range(total):
        w["l.experts.%d.up" % e] = rng.standard_normal((d, f)) * 0.3
        w["l.experts.%d.down" % e] = rng.standard_normal((f, d)) * 0.3
    w = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
    config = dict(CONFIG, num_experts_per_tok=2)
    rows = x.reshape(-1, d)
    with jax.default_matmul_precision("highest"):
        idx, gates, _ = moe.sigmoid_topk_route(
            rows, w["l.router.w"], w["l.router.b"], 2, 2.5, True)
        parts, counted = [], 0
        for first in (0, 2, 4, 6):
            up = jnp.stack([w["l.experts.%d.up" % e]
                            for e in (first, first + 1)])
            down = jnp.stack([w["l.experts.%d.down" % e]
                              for e in (first, first + 1)])
            out, got_rows = moe.held_experts_ffn(
                rows, idx, gates, None, up, down, first=first, total=total,
                activation="relu2")
            parts.append(out)
            counted += int(got_rows.sum())
        shared = REF._relu2_mlp(rows, w["l.shared.up.w"],
                                w["l.shared.down.w"])
        want = REF.expert_layer(x, w, "l", config, 0).reshape(-1, d)
    np.testing.assert_allclose(sum(parts) + shared, want, rtol=1e-4,
                               atol=1e-5)
    assert counted == rows.shape[0] * 2
    assert np.abs(parts[0] + shared - want).max() > 1e-2


# -- the model against the reference ------------------------------------------

def _feed(seed=0, t=T):
    ids = np.random.default_rng(seed).integers(
        0, CONFIG["vocab_size"], size=(B, t + 1)).astype("int64")
    return {"input_ids": np.ascontiguousarray(ids[:, :-1]),
            "labels": np.ascontiguousarray(ids[:, 1:])}


def _build(config, t=T, backward=False, recompute=True, **extra):
    cfg = dict(BUILDER.model_config(config, recompute), **extra)
    with fluid.unique_name.guard():
        main, startup, _, loss = decoder.build_train(
            cfg, seq_len=t, lr=1e-3, amp=False, train=False)
        grads = {}
        if backward:
            with fluid.program_guard(main, startup):
                grads = {p.name: g.name for p, g in
                         fluid.backward.append_backward(loss)}
    main.random_seed = startup.random_seed = 5
    return main, startup, loss, grads


def _weights():
    scope = fluid.global_scope()
    return {n: np.asarray(scope.get(n)) for n in scope.local_var_names()
            if n.startswith("decoder.")}


@pytest.mark.parametrize("recompute", [True, False])
def test_forward_logits_loss_gates_and_mixer_match_the_reference(recompute):
    """Layers of one part by the pattern; the first Mamba-2 mixer's
    grouped gated norm, the first expert layer's gates for the rows its
    router read, the logits through the untied head and the loss."""
    main, startup, loss, _ = _build(CONFIG, recompute=recompute,
                                    keep_router_input=True)
    ops = [op for b in main.blocks for op in b.ops]
    head = [op for op in ops if op.type == "softmax_with_cross_entropy"][0]
    route = [op for op in ops if op.type == "moe_route"][0]
    mixer = [op.input("X")[0] for op in ops if op.type == "mul"
             and op.input("Y")[0] == "decoder.layer0.mixer.out_proj.w"][0]
    assert not any(op.type == "rotary_embedding" for op in ops)
    assert [op.attrs.get("activation") for op in ops
            if op.type == "moe_experts"] == ["relu2", "relu2"]
    assert "decoder.layer1.moe.experts.0.gate" not in {
        v.name for v in main.list_vars()}
    feed = _feed(0)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        logits, got, gates, normed = exe.run(main, feed=feed, fetch_list=[
            head.input("Logits")[0], loss, route.output("Gate")[0], mixer])
        with jax.default_matmul_precision("highest"):
            want = REF.forward(_weights(), feed, CONFIG)
    np.testing.assert_allclose(logits, want["logits"], atol=2e-5, rtol=2e-4)
    assert abs(float(got[0]) - float(want["loss"])) < 1e-5
    assert _rel(normed, want["mamba_first"]) < 1e-5
    assert _rel(gates, want["route_gates"]) < 1e-5
    assert abs(float(want["loss"]) - np.log(CONFIG["vocab_size"])) < 0.1


GRADIENT_OF = [
    "decoder.layer0.mixer.in_proj.w", "decoder.layer0.mixer.conv.w",
    "decoder.layer2.mixer.conv.b", "decoder.layer2.mixer.A_log",
    "decoder.layer0.mixer.dt_bias", "decoder.layer2.mixer.D",
    "decoder.layer0.mixer.norm.scale", "decoder.layer2.mixer.out_proj.w",
    "decoder.layer1.moe.experts.0.up", "decoder.layer1.moe.experts.1.down",
    "decoder.layer4.moe.shared.up.w", "decoder.layer4.moe.shared.down.w",
    "decoder.layer1.moe.router.w", "decoder.layer3.attn.q.w",
    "decoder.layer3.attn.k.w", "decoder.layer3.attn.v.w",
    "decoder.layer3.attn.o.w", "decoder.layer4.ln1.scale",
    "decoder.embed", "decoder.head.w"]


@pytest.fixture(scope="module")
def gradients():
    """The program's gradients of one parameter of each kind (one step
    through ``append_backward``, every layer in a recompute region) and
    ``jax.grad`` of the reference's loss for the same parameters, on the
    same weights and feed."""
    main, startup, loss, grads = _build(CONFIG, backward=True)
    feed = _feed(1)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        w = _weights()
        got = exe.run(main, feed=feed,
                      fetch_list=[grads[n] for n in GRADIENT_OF])
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda p: REF.loss(dict(w, **p), feed, CONFIG))(
            {n: jnp.asarray(w[n]) for n in GRADIENT_OF})
    return dict(zip(GRADIENT_OF, got)), want


def _parts(name):
    """in_proj's columns z, x, B, C and Δ, and the conv's channels x, B
    and C, each held apart (a wrong dB or dC moves its own columns by
    their size, the whole matrix by a fraction of a percent)."""
    inner = CONFIG["mamba_num_heads"] * CONFIG["mamba_head_dim"]
    state = CONFIG["n_groups"] * CONFIG["ssm_state_size"]
    if name.endswith("in_proj.w"):
        widths, axis = dict(z=inner, x=inner, B=state, C=state,
                            dt=CONFIG["mamba_num_heads"]), 1
    elif ".conv." in name:
        widths, axis = dict(x=inner, B=state, C=state), 0
    else:
        return {"all": slice(None)}
    cuts = np.cumsum([0] + list(widths.values()))
    return {k: (slice(None),) * axis + (slice(lo, hi),)
            for k, lo, hi in zip(widths, cuts[:-1], cuts[1:])}


@pytest.mark.parametrize("name", GRADIENT_OF)
def test_gradient_matches_jax_grad_of_the_reference(name, gradients):
    got, want = gradients[0][name], np.asarray(gradients[1][name])
    for part, cut in _parts(name).items():
        assert np.abs(want[cut]).max() > 0, part
        np.testing.assert_allclose(got[cut], want[cut], atol=1e-4 * np.abs(
            want).max(), rtol=2e-3, err_msg=part)


def test_training_through_the_grouped_kernels_lowers_the_loss(monkeypatch):
    """Mamba-2 mixers of 16 heads of 32 in 2 groups (a head block of 8 a
    group) through the scan kernels in interpret mode, bf16 AMP, every
    layer in a region: the loss falls, each layer's forward kernel runs
    once (what it gave crosses the region), and the relu² experts' kept
    products cross their regions too."""
    from paddle_tpu.observability import metrics, tracing

    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
    config = dict(CONFIG, mamba_num_heads=16, mamba_head_dim=32,
                  chunk_size=128, hybrid_override_pattern="ME",
                  num_hidden_layers=2)
    cfg = BUILDER.model_config(config, True)
    with fluid.unique_name.guard():
        main, startup, _, loss = decoder.build_train(
            cfg, seq_len=256, lr=3e-3, amp=True, train=True)
    main.random_seed = startup.random_seed = 7
    feed = _feed(2, 256)
    metrics.registry().reset()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        with tracing.span("test.root"):     # inside a trace a step records
            losses = [float(exe.run(main, feed=feed, fetch_list=[loss])[0]
                            .reshape(-1)[0]) for _ in range(3)]
    assert losses[-1] < losses[0]
    chunks = {dict(m.labels)["pass"]: m.value
              for m in metrics.registry().collect()
              if m.name == "ssd_chunks_total"}
    # 1 Mamba layer x 2 sequences x 2 chunks x 16 heads, each pass
    assert chunks == {"forward": 64, "backward": 64}
    sites = collections.Counter(
        dict(m.labels)["op_type"] for m in metrics.registry().collect()
        if m.name == "grad_residual_sites_total"
        and dict(m.labels)["path"] == "kept_across_region"
        for _ in range(int(m.value)))
    assert sites["ssd_scan"] == 1 and sites["moe_experts"] == 1
    metrics.registry().reset()


def test_one_part_layers_and_groups_are_built_and_a_bad_part_is_not():
    with fluid.unique_name.guard():
        main = _build(CONFIG)[0]
    names = {p.name for p in main.all_parameters()}
    # one norm a layer, no second (ln2) and no MLP beside a part
    assert {n for n in names if ".ln2." in n or ".mlp." in n} == set()
    assert sum(1 for n in names if n.endswith(".ln1.scale")) == 5
    conv = main.global_block().var("decoder.layer0.mixer.conv.w")
    assert conv.shape[0] == 4 * 32 + 2 * 2 * 16
    with fluid.unique_name.guard(), pytest.raises(ValueError):
        _build(dict(CONFIG, hybrid_override_pattern="MEX*E"))


def test_the_fp8_control_fails_the_cells_limits_at_a_small_size():
    """``tools/reference_control.py`` after a short window at
    ``selftest/test_nemotron.py``'s small size, under the nemotron cell's
    own limits: the comparison the cell makes is within them, and the
    same comparison against the reference on fp8 weight matrices
    (``reference/fp8_e4m3.py``) is not."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import reference_control

    from chipbench.selftest import test_nemotron as selftest

    driver = mf.load_by_name("traffic", "train_loop")
    limits = mf.load_cell("nemotron_3_nano_30b_a3b_train_seq8192_bs2")[
        "config"]["reference"]["tolerance"]
    config = dict(CONFIG, reference=dict(CONFIG["reference"],
                                         tolerance=limits))
    cell = {"config": config, "traffic": selftest.NEMOTRON_TINY_TRAFFIC,
            "workload": {"name": "tiny", "chips": 1, "require_kernels": [],
                         "require_collectives": False,
                         "program": {"recompute": True}}}
    with fluid.scope_guard(fluid.Scope()):
        state = driver.drive(cell, 4200000207 % (2 ** 31 - 1), 0.5)
        sound = reference_control.readings(driver, state, cell, "nemotron")
        control = reference_control.readings(driver, state, cell,
                                             "fp8_e4m3")
    assert sound[0] == [] and control[0]
    for found in (sound[1], control[1]):
        assert set(found) == {"loss_abs"} | {
            "%s_%s" % (k, kind) for k, v in limits.items() for kind in v}
