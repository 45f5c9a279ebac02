"""The hybrid decoder (``models/decoder.py`` with ``layer_types`` ``mamba``
and ``attention``: Mamba-2 mixers, grouped-query attention with no
positional encoding, the published multipliers, a tied head) against its
plain reference ``chipbench/reference/granite.py`` at ``GRANITE_TINY`` on
the CPU, and the pieces it forced: the SSD scan kernels of
``ops/pallas/ssd_scan.py`` (interpret mode) and their XLA twin against the
reference's recurrence as written, the causal conv and the gated norm,
the mixer's start, and what the scan's counter says."""

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
from paddle_tpu.models import decoder  # noqa: E402
from paddle_tpu.ops.pallas import ssd_scan as S  # noqa: E402

REF = mf.load_by_name("reference", "granite")

# hidden 64, a Mamba-2 layer of 2 heads of 64 with a state of 16, an
# attention layer of 4 query heads on 2 key-value heads of 16, a second
# Mamba-2 layer; chunks of 16 over 48 positions: the state crosses two
CFG = dict(decoder.GRANITE_TINY)
T, B = 48, 2


def _scan_inputs(seed, bt, t, h, p, n):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((bt, t, h, p))
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (bt, t, h)))
    a = -rng.uniform(1, 16, h)
    b, c = rng.standard_normal((2, bt, t, n))
    d = rng.standard_normal(h)
    return [jnp.asarray(v, jnp.float32) for v in (x, dt, a, b, c, d)]


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


# -- the scan against the recurrence -------------------------------------------

@pytest.mark.parametrize("path,t,h,p,chunk,block", [
    ("interpret", 384, 4, 64, 128, 16),    # three chunks, one head block
    ("interpret", 300, 16, 16, 128, 8),    # a padded tail, two head blocks
    ("interpret", 256, 2, 128, 128, 16),   # a head a tile
    ("off", 100, 3, 8, 16, 16),            # XLA's chunks, any widths
])
def test_scan_matches_the_recurrence_in_value_and_gradient(
        path, t, h, p, chunk, block, monkeypatch):
    """Forward and the gradients of x, Δ, A, B, C and D, the state
    carried over chunks (Δ down to 1e-3 with A as small as 1: a head
    remembers hundreds of positions)."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS", path)
    monkeypatch.setattr(S, "HEADS_A_BLOCK", block)
    if path == "interpret":
        assert S._heads_a_block(h, p) == min(block, h)
    args = _scan_inputs(0, 2, t, h, p, 16)
    w = jnp.asarray(np.random.default_rng(1).standard_normal(
        (2, t, h, p)), jnp.float32)
    assert S.routes_to_kernel(args[0], args[3], chunk) == (
        path == "interpret")
    with jax.default_matmul_precision("highest"):
        y = S.ssd_scan(*args, chunk=chunk)
        want = REF.scan(*args)
        assert _rel(y, want) < 1e-5
        grads = jax.grad(lambda *a: jnp.sum(S.ssd_scan(*a, chunk=chunk)
                                            * w), argnums=range(6))(*args)
        wants = jax.grad(lambda *a: jnp.sum(REF.scan(*a) * w),
                         argnums=range(6))(*args)
    for name, got, ref in zip("x dt a b c d".split(), grads, wants):
        assert _rel(got, ref) < 1e-4, name


def test_the_kernels_bf16_products_keep_every_gradient_close(monkeypatch):
    """The kernels as the chip runs them under AMP: the products' operands
    rounded to bf16, accumulated in float32 (the interpreter's dots take
    float32, so the operands are rounded and cast up, which is exact).  In
    d(cum) the later and the earlier end of each within-chunk product
    cancel on the diagonal; taken from differently rounded products they
    left dA 20% and dΔ 0.9% off here (2.7% and 0.64% on the chip at the
    cell's widths, ``chip_smoke.py --probe ssd_scan``)."""
    def upcast(dims):
        return lambda a, b: jax.lax.dot_general(
            a.astype(jnp.float32), b.astype(jnp.float32), (dims, ((), ())),
            preferred_element_type=jnp.float32)

    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
    monkeypatch.setattr(S, "_operands", lambda x, interpret: jnp.bfloat16)
    monkeypatch.setattr(S, "_dot", upcast(((1,), (0,))))
    monkeypatch.setattr(S, "_dot_t", upcast(((1,), (1,))))
    x, dt, a, b, c, d = _scan_inputs(6, 1, 1024, 16, 64, 128)
    x, b, c = (v.astype(jnp.bfloat16) for v in (x, b, c))
    dy = jnp.asarray(np.random.default_rng(7).standard_normal(x.shape),
                     jnp.bfloat16)
    _, pullback = jax.vjp(lambda *v: S.ssd_scan(*v, chunk=256),
                          x, dt, a, b, c, d)
    with jax.default_matmul_precision("highest"):
        _, plain = jax.vjp(REF.scan, *(v.astype(jnp.float32)
                                       for v in (x, dt, a, b, c, d)))
        wants = plain(dy.astype(jnp.float32))
    for name, got, want in zip("x dt a b c d".split(), pullback(dy), wants):
        assert _rel(got, want) < 0.015, name


def test_the_state_crosses_chunks(monkeypatch):
    """Resetting the state at each chunk's start changes what a later
    chunk gives: what the test above holds the kernels to carries."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
    x, dt, a, b, c, d = _scan_inputs(3, 1, 256, 2, 64, 16)
    whole = S.ssd_scan(x, dt, a, b, c, d, chunk=128)
    alone = S.ssd_scan(x[:, 128:], dt[:, 128:], a, b[:, 128:], c[:, 128:],
                       d, chunk=128)
    assert _rel(alone, whole[:, 128:]) > 1e-3


def test_a_kept_forward_gives_the_same_gradients(monkeypatch):
    """What a recompute region keeps (y and each chunk's first state)
    handed back to the re-run: the same value and gradients, and no
    forward kernel traced a second time."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
    args = _scan_inputs(4, 1, 256, 2, 64, 16)
    y, kept = S.ssd_scan(*args, chunk=128, return_residuals=True)
    assert len(kept) == 2 and kept[1].shape == (1, 2, 16, 128)

    def loss(*a, residuals=None):
        return jnp.sum(S.ssd_scan(*a, chunk=128, residuals=residuals) ** 2)

    counts = collections.Counter()
    with S.noting_chunks(counts):
        got = jax.grad(lambda *a: loss(*a, residuals=kept),
                       argnums=range(6))(*args)
    assert counts == {"backward": 2 * 2}
    want = jax.grad(loss, argnums=range(6))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-7)


def test_one_small_kernel_a_pass(monkeypatch):
    """A site traces one forward and one backward ``pallas_call``, the
    heads and chunks a grid and not bodies unrolled over chunks."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
    args = _scan_inputs(5, 1, 512, 4, 64, 16)
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(S.ssd_scan(*a, chunk=128)),
        argnums=range(6)))(*args))
    assert jaxpr.count("name=ssd_scan_fwd") == 1
    assert jaxpr.count("name=ssd_scan_bwd") == 1


# -- the new ops ---------------------------------------------------------------

def _run(build, feed):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        out = build()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        got = exe.run(main, feed=feed, fetch_list=[out])[0]
        scope = fluid.global_scope()
        params = {v.name: np.asarray(scope.get(v.name))
                  for v in main.all_parameters()}
    return got, params


def test_causal_conv1d_is_the_left_padded_convolution():
    rng = np.random.default_rng(6)
    x = rng.standard_normal((2, 9, 5)).astype("float32")

    def build():
        v = fluid.layers.data("x", shape=[9, 5], dtype="float32")
        return fluid.layers.causal_conv1d(
            v, 4, param_attr=fluid.ParamAttr(
                name="w", initializer=fluid.initializer.Normal(0, 1)),
            bias_attr=fluid.ParamAttr(
                name="b", initializer=fluid.initializer.Normal(0, 1)))

    got, p = _run(build, {"x": x})
    w, b = p["w"], p["b"]
    assert w.shape == (5, 4)
    padded = np.concatenate([np.zeros((2, 3, 5), "float32"), x], axis=1)
    want = np.stack([sum(padded[:, t + k] * w[:, k] for k in range(4))
                     for t in range(9)], axis=1) + b
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_rms_norm_gated_normalises_the_gated_product():
    rng = np.random.default_rng(7)
    x, z = rng.standard_normal((2, 2, 3, 8)).astype("float32")

    def build():
        u = fluid.layers.data("x", shape=[3, 8], dtype="float32")
        g = fluid.layers.data("z", shape=[3, 8], dtype="float32")
        return fluid.layers.rms_norm_gated(
            u, g, epsilon=1e-5, param_attr=fluid.ParamAttr(
                name="s", initializer=fluid.initializer.Normal(1, 0.5)))

    got, p = _run(build, {"x": x, "z": z})
    g = x * z / (1 + np.exp(-z))
    want = g / np.sqrt((g * g).mean(-1, keepdims=True) + 1e-5) * p["s"]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_the_mixer_starts_as_mamba_ssm_starts_it():
    """A ~ U[1, 16]; softplus(dt_bias) ~ logU[1e-3, 1e-1]; D = 1; the
    conv's filter and bias within +-1/2."""
    cfg = dict(CFG, mamba_n_heads=64, mamba_d_head=2,
               mamba_expand=2 * 64 // 64)
    with fluid.unique_name.guard():
        main, startup, _, _ = decoder.build_train(cfg, seq_len=16,
                                                  train=False)
    startup.random_seed = 11
    with fluid.scope_guard(fluid.Scope()):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        scope = fluid.global_scope()
        p = "decoder.layer0.mixer."
        a = np.exp(np.asarray(scope.get(p + "A_log")))
        dt = np.log1p(np.exp(np.asarray(scope.get(p + "dt_bias"),
                                        "float64")))
        d = np.asarray(scope.get(p + "D"))
        conv = np.asarray(scope.get(p + "conv.w"))
        bias = np.asarray(scope.get(p + "conv.b"))
    assert a.min() >= 1 and a.max() <= 16 and a.std() > 2
    assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 0.1 * 1.001
    assert np.log(dt).std() > 0.5              # spread in log, not linear
    assert (d == 1).all()
    assert np.abs(conv).max() <= 0.5 and conv.std() > 0.2
    assert np.abs(bias).max() <= 0.5 and bias.std() > 0.2


def test_only_one_group_and_no_projection_bias_are_built():
    """A projection bias is refused.  Groups of B and C are built since
    Nemotron-H (``tests/test_nemotron.py``), and the mixer's width is
    ``mamba_n_heads * mamba_d_head`` whatever ``mamba_expand`` says; at
    the default of one group the scan takes B and C as [B, T, N] and the
    gated norm normalises the whole width, as granite's layers do."""
    with fluid.unique_name.guard(), pytest.raises(ValueError):
        decoder.build_train(dict(CFG, mamba_proj_bias=True), seq_len=16)
    for cfg, groups in ((CFG, 1), (dict(CFG, mamba_n_groups=2), 2),
                        (dict(CFG, mamba_expand=3), 1)):
        with fluid.unique_name.guard():
            main = decoder.build_train(cfg, seq_len=16)[0]
        ops = [op for blk in main.blocks for op in blk.ops]
        scan = [op for op in ops if op.type == "ssd_scan"][0]
        b = scan.block._find_var_recursive(scan.input("B")[0])
        assert len(b.shape) == (3 if groups == 1 else 4)
        norm = [op for op in ops if op.type == "rms_norm_gated"][0]
        assert ("group_size" in norm.attrs) == (groups > 1)


# -- the model against the reference -------------------------------------------

def _feed(seed=0, t=T):
    ids = np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], size=(B, t + 1)).astype("int64")
    return {"input_ids": np.ascontiguousarray(ids[:, :-1]),
            "labels": np.ascontiguousarray(ids[:, 1:])}


def _build(cfg, t=T, backward=False, amp=False):
    with fluid.unique_name.guard():
        main, startup, _, loss = decoder.build_train(
            cfg, seq_len=t, lr=1e-3, amp=amp, train=False)
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


@pytest.mark.parametrize("recompute,path", [
    (True, "off"), (False, "off"), (True, "interpret")])
def test_forward_logits_loss_and_both_mixers_match_the_reference(
        recompute, path, monkeypatch):
    """The embedding times 12, both branches times 0.22, the logits over
    8 through the tied embedding; the first Mamba-2 mixer's output before
    W_out and the attention before W_o.  ``interpret``: the scan kernels
    at chunks of 128 over 300 positions."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS", path)
    t = 300 if path == "interpret" else T
    cfg = dict(CFG, recompute=recompute,
               mamba_chunk_size=128 if path == "interpret" else 16)
    main, startup, loss, _ = _build(cfg, t)
    ops = [op for b in main.blocks for op in b.ops]
    head = [op for op in ops if op.type == "softmax_with_cross_entropy"][0]

    def before(name):
        return [op.input("X")[0] for op in ops if op.type == "mul"
                and op.input("Y")[0] == name][0]

    assert not any(op.type == "rotary_embedding" for op in ops)
    assert "decoder.head.w" not in {v.name for v in main.list_vars()}
    feed = _feed(0, t)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        logits, got, mixer, attn = exe.run(main, feed=feed, fetch_list=[
            head.input("Logits")[0], loss,
            before("decoder.layer0.mixer.out_proj.w"),
            before("decoder.layer1.attn.o.w")])
        with jax.default_matmul_precision("highest"):
            want = REF.forward(_weights(), feed, cfg)
    np.testing.assert_allclose(logits, want["logits"], atol=2e-5, rtol=2e-4)
    assert abs(float(got[0]) - float(want["loss"])) < 1e-5
    assert _rel(mixer, want["mamba_first"]) < 1e-5
    assert _rel(attn, want["attention_first"]) < 1e-5
    assert abs(float(want["loss"]) - np.log(CFG["vocab_size"])) < 0.1


GRADIENT_OF = [
    "decoder.layer0.mixer.in_proj.w", "decoder.layer0.mixer.conv.w",
    "decoder.layer2.mixer.conv.b", "decoder.layer2.mixer.A_log",
    "decoder.layer0.mixer.dt_bias", "decoder.layer2.mixer.D",
    "decoder.layer0.mixer.norm.scale", "decoder.layer2.mixer.out_proj.w",
    "decoder.layer1.attn.q.w", "decoder.layer1.mlp.up.w", "decoder.embed"]


# ``off``: float32, XLA's chunks of 16 over 48 positions; ``interpret``:
# the scan kernels at chunks of 128 over 256 positions, bf16 AMP (the
# kernels take bf16 x, B and C, as on the chip) and what each forward
# kernel gave kept across its recompute region for the backward kernel
GRADIENT_PATHS = {"off": dict(t=T, chunk=16, amp=False, rel=None),
                  "interpret": dict(t=256, chunk=128, amp=True, rel=0.03)}


@pytest.fixture(scope="module", params=sorted(GRADIENT_PATHS))
def gradients(request):
    """The program's gradients of one parameter of each kind (one step
    through ``append_backward``) and ``jax.grad`` of the reference's
    loss for the same parameters, on the same weights and feed; and the
    chunks each pass of the scan kernels ran."""
    from paddle_tpu.observability import metrics, tracing

    path = GRADIENT_PATHS[request.param]
    metrics.registry().reset()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("PADDLE_TPU_PALLAS", request.param)
        main, startup, loss, grads = _build(
            dict(CFG, mamba_chunk_size=path["chunk"]), path["t"],
            backward=True, amp=path["amp"])
        feed = _feed(1, path["t"])
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            w = _weights()
            with tracing.span("test.root"):  # inside a trace a step records
                got = exe.run(main, feed=feed,
                              fetch_list=[grads[n] for n in GRADIENT_OF])
    chunks = {dict(m.labels)["pass"]: m.value
              for m in metrics.registry().collect()
              if m.name == "ssd_chunks_total"}
    metrics.registry().reset()
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda p: REF.loss(dict(w, **p), feed, CFG))(
            {n: jnp.asarray(w[n]) for n in GRADIENT_OF})
    return dict(zip(GRADIENT_OF, got)), want, chunks, path


def _parts(name):
    """A gradient's slices that the scan's inputs feed apart: in_proj's
    columns z, x, B, C and Δ, and the conv's channels x, B and C (a
    wrong dB moves B's columns by its own size, and the whole matrix by a
    fraction of a percent); every other parameter whole."""
    inner, state = CFG["mamba_n_heads"] * CFG["mamba_d_head"], \
        CFG["mamba_d_state"]
    if name.endswith("in_proj.w"):
        widths, axis = dict(z=inner, x=inner, B=state, C=state,
                            dt=CFG["mamba_n_heads"]), -1
    elif ".conv." in name:
        widths, axis = dict(x=inner, B=state, C=state), 0
    else:
        return {"all": slice(None)}
    cuts = np.cumsum([0] + list(widths.values()))
    return {k: (slice(None),) * (axis % 2) + (slice(lo, hi),)
            for k, lo, hi in zip(widths, cuts[:-1], cuts[1:])}


@pytest.mark.parametrize("name", GRADIENT_OF)
def test_gradient_matches_jax_grad_of_the_reference(name, gradients):
    """One parameter of each kind, through ``append_backward`` and the
    recompute regions' own ``jax.vjp``; the tied embedding's is the sum of
    what the lookup and the head give it.  Under AMP each part within 3%
    (rel. L2) of float32's."""
    got, want = gradients[0][name], np.asarray(gradients[1][name])
    for part, cut in _parts(name).items():
        assert np.abs(want[cut]).max() > 0, part
        if gradients[3]["rel"] is None:
            np.testing.assert_allclose(got[cut], want[cut], atol=1e-4 * np.abs(
                want).max(), rtol=2e-3, err_msg=part)
        else:
            assert _rel(got[cut], want[cut]) < gradients[3]["rel"], part


def test_the_gradients_went_through_the_kernels(gradients):
    """2 Mamba layers x 2 sequences x 2 chunks x 2 heads a pass on the
    ``interpret`` path, the forward once (kept across the region); none
    on XLA's."""
    on_kernels = gradients[3]["chunk"] == 128
    assert gradients[2] == ({"forward": 16, "backward": 16} if on_kernels
                            else {})


def test_training_lowers_the_loss_and_counts_the_scans_chunks(monkeypatch):
    """Through the kernels (interpret mode), bf16 AMP and recompute: what
    the forward kernel gave crosses each region, so the step runs each
    layer's forward kernel once; ``ssd_chunks_total`` says so."""
    from paddle_tpu.observability import metrics, tracing

    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
    cfg = dict(CFG, mamba_chunk_size=128)
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
            first = float(exe.run(main, feed=feed,
                                  fetch_list=[loss])[0].reshape(-1)[0])
        losses = [first] + [float(exe.run(
            main, feed=feed, fetch_list=[loss])[0].reshape(-1)[0])
            for _ in range(3)]
    assert losses[-1] < losses[0]
    chunks = {dict(m.labels)["pass"]: m.value
              for m in metrics.registry().collect()
              if m.name == "ssd_chunks_total"}
    # 2 Mamba layers x 2 sequences x 2 chunks x 2 heads, each pass
    assert chunks == {"forward": 16, "backward": 16}
    sites = collections.Counter(
        dict(m.labels)["op_type"] for m in metrics.registry().collect()
        if m.name == "grad_residual_sites_total"
        and dict(m.labels)["path"] == "kept_across_region"
        for _ in range(int(m.value)))
    assert sites["ssd_scan"] == 2
    attrs = [r["attrs"] for r in tracing.get_tracer().records()
             if r["name"] == "executor.compile"
             and "ssd_chunks_forward" in r["attrs"]][-1]
    assert attrs["ssd_chunks_forward"] == attrs["ssd_chunks_backward"] == 16
    assert "ssd_chunks_region_rerun" not in attrs
    metrics.registry().reset()


def test_the_fp8_control_fails_the_cells_limits_at_a_small_size():
    """``tools/reference_control.py`` after a short window at
    ``selftest/test_granite.py``'s small size, under the granite cell's
    own limits: the comparison the cell makes is within them, and the
    same comparison against the reference on fp8 weight matrices
    (``reference/fp8_e4m3.py``) is not; the loss is read in both though
    the cell sets it no limit."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import reference_control

    from chipbench.selftest import test_granite as selftest

    driver = mf.load_by_name("traffic", "train_loop")
    limits = mf.load_cell("granite_4_0_h_micro_train_seq8192_bs1")[
        "config"]["reference"]["tolerance"]
    assert "loss" not in limits
    config = dict(selftest.GRANITE_TINY, reference=dict(
        selftest.GRANITE_TINY["reference"], tolerance=limits))
    cell = {"config": config, "traffic": selftest.GRANITE_TINY_TRAFFIC,
            "workload": {"name": "tiny", "chips": 1, "require_kernels": [],
                         "require_collectives": False,
                         "program": {"recompute": True}}}
    with fluid.scope_guard(fluid.Scope()):
        state = driver.drive(cell, 3800000207 % (2 ** 31 - 1), 0.5)
        sound = reference_control.readings(driver, state, cell, "granite")
        control = reference_control.readings(driver, state, cell,
                                             "fp8_e4m3")
    assert sound[0] == [] and control[0]
    for found in (sound[1], control[1]):
        assert set(found) == {"loss_abs"} | {k + "_rel_l2" for k in limits}
    assert control[1]["mamba_first_rel_l2"] > 3 * sound[1][
        "mamba_first_rel_l2"]
