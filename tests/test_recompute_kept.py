"""A recompute region runs each flash forward kernel once
(``ops/control_flow.py``, ``registry.RegionKept``): where the forward
``recompute_block`` and its grad op are lowered by one call, a flash site
that routes to the Mosaic kernel keeps the kernel's ``(o, m, l)`` across
the region, and the grad op's re-run, which computes Q, K and V again,
hands them to the backward kernels and runs no forward kernel.  Held
against the same program lowered the parent's way (forward ops and grad
ops in two calls: nothing crosses, the region's forward kernel runs
again).  CPU, kernels in interpret mode with the debug hash mask."""

import collections
import functools
import re
import types

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import executor as E
from paddle_tpu.executor import Scope, scope_guard
from paddle_tpu.models import bert, decoder
from paddle_tpu.observability import metrics, tracing
from paddle_tpu.ops import control_flow, registry

T = 128
FLASH = "fused_multihead_attention"
# name: (builder, flash sites in regions); every layer is one region
CASES = {
    # attention dropout off and on (the re-run draws the forward's mask)
    "bert_dropout0": ("bert", 0.0, 2),
    "bert_dropout0.1": ("bert", 0.1, 2),
    # latent attention: d_qk 24 against d_v 16
    "mla": ("decoder", dict(decoder.DECODER_TINY, num_hidden_layers=2), 2),
    # 4 query heads on 2 key-value heads; a window of 24 keys, and none
    "gqa_window": ("decoder", dict(
        decoder.MELLUM_TINY, num_hidden_layers=2,
        layer_types=["sliding_attention", "full_attention"]), 2),
}


@pytest.fixture(autouse=True)
def _interpret_debug_env(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
    monkeypatch.setenv("PADDLE_TPU_FLASH_DROPOUT_DEBUG", "iota")
    metrics.registry().reset()


def _build(case, train=True):
    kind, arg, _ = CASES[case]
    fluid.unique_name.switch()
    if kind == "bert":
        cfg = bert.BertConfig(vocab_size=128, hidden=128, layers=2, heads=2,
                              ffn=256, max_seq=T, dropout=arg, fused_ln=True,
                              fuse_attn=True, recompute=True)
        main, startup, _, loss = bert.build_pretrain(cfg, seq_len=T, lr=1e-3,
                                                    train=train)
        feed = bert.make_fake_batch(2, T, cfg, np.random.RandomState(2))
    else:
        main, startup, _, loss = decoder.build_train(arg, seq_len=T, lr=1e-3,
                                                     train=train)
        ids = np.random.default_rng(0).integers(
            0, arg["vocab_size"], size=(2, T + 1)).astype("int64")
        feed = {"input_ids": np.ascontiguousarray(ids[:, :-1]),
                "labels": np.ascontiguousarray(ids[:, 1:])}
    main.random_seed = startup.random_seed = 11
    return main, startup, loss, feed


def _step_jaxpr(feed):
    """The jaxpr of the step the Executor compiled last."""
    cb = E._LAST_COMPILED_BLOCK
    sc = fluid.global_scope()
    rw = {n: sc.get(n) for n in cb.rw_names}
    ro = {n: sc.get(n) for n in cb.ro_names}
    fv = {n: jnp.asarray(feed[n]) for n in cb.feed_names}
    return cb.jitted.trace(fv, rw, ro, E.rng_key(0)).jaxpr


def _kernels(jaxpr):
    return collections.Counter(re.findall(
        r"\bname=((?:fused_ln|flash_attention)_\w+)", str(jaxpr)))


def _sites():
    return {(dict(m.labels)["op_type"], dict(m.labels)["path"]): m.value
            for m in metrics.registry().collect()
            if m.name == "grad_residual_sites_total"}


def _train(case, steps=2):
    """``steps`` steps: (losses as bytes, the first step's gradient of
    every parameter as bytes by name, the step's jaxpr, the counter)."""
    metrics.registry().reset()
    main, startup, loss, feed = _build(case)
    grads = [p.name + "@GRAD" for p in main.all_parameters()
             if p.name + "@GRAD" in main.global_block().vars]
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(Scope()):
        exe.run(startup)
        outs = [exe.run(main, feed=feed, fetch_list=[loss] + grads)
                for _ in range(steps)]
        losses = [np.asarray(out[0]).tobytes() for out in outs]
        first = {n: np.asarray(g).tobytes()
                 for n, g in zip(grads, outs[0][1:])}
        return losses, first, _step_jaxpr(feed), _sites()


_kept_run = functools.lru_cache(maxsize=None)(_train)


def _parents_lowering(monkeypatch):
    """The forward ops and the rest of every op list in two
    ``_run_ops_into_env`` calls: no region's two ops meet in one, so
    nothing crosses a region and its forward kernel runs again."""
    whole = E._run_ops_into_env

    def in_two_calls(block, env, ctx, ops=None):
        ops = list(block.ops if ops is None else ops)
        cut = next((i for i, op in enumerate(ops)
                    if op.type.endswith("_grad")), len(ops))
        whole(block, env, ctx, ops=ops[:cut])
        return whole(block, env, ctx, ops=ops[cut:])

    monkeypatch.setattr(E, "_run_ops_into_env", in_two_calls)


@pytest.mark.parametrize("case", CASES)
def test_one_forward_kernel_a_site_in_a_region(case):
    """One forward, one dK/dV and one dQ kernel a site, and the counter
    says every site's statistics crossed its region."""
    _, _, jaxpr, sites = _kept_run(case)
    n = CASES[case][2]
    kernels = _kernels(jaxpr)
    assert {k: kernels[k] for k in (
        "flash_attention_fwd", "flash_attention_dkv",
        "flash_attention_dq")} == {
        "flash_attention_fwd": n, "flash_attention_dkv": n,
        "flash_attention_dq": n}, kernels
    assert sites[FLASH, "kept_across_region"] == n
    assert (FLASH, "recomputed") not in sites


@pytest.mark.parametrize("case", CASES)
def test_equal_to_the_parents_lowering_bit_for_bit(case, monkeypatch):
    """The parent's lowering holds two forward kernels a site and the
    same backward kernels; on the CPU in float32 forward and re-run
    compute the same Q, K and V, so the backward kernels read the same
    ``o, m, l`` either way: losses of two steps and the first step's
    gradient of every parameter are the same bytes."""
    kept = _kept_run(case)
    _parents_lowering(monkeypatch)
    parent = _train(case)
    n = CASES[case][2]
    kernels = _kernels(parent[2])
    assert (kernels["flash_attention_fwd"], kernels["flash_attention_dkv"],
            kernels["flash_attention_dq"]) == (2 * n, n, n)
    assert parent[3][FLASH, "recomputed"] == n
    assert (FLASH, "kept_across_region") not in parent[3]
    assert kept[0] == parent[0]
    assert kept[1].keys() == parent[1].keys() and len(kept[1]) > 8
    assert [k for k in kept[1] if kept[1][k] != parent[1][k]] == []


@pytest.mark.parametrize("how", ["for_test_clone", "predictor_export"])
@pytest.mark.parametrize("case", ["bert_dropout0.1", "gqa_window"])
def test_forward_only_programs_with_regions_lower_as_before(
        case, how, tmp_path, monkeypatch):
    """No grad op in the op list: no region shares anything (making a
    ``RegionKept`` raises here), every site takes the plain entry (one
    forward kernel, no ``_flash_kept``), no site is counted."""
    def refuse(*a, **k):
        raise AssertionError("a forward-only lowering made a RegionKept")

    monkeypatch.setattr(registry, "RegionKept", refuse)
    monkeypatch.setattr(control_flow, "RegionKept", refuse)
    main, startup, loss, feed = _build(case, train=False)
    exe = fluid.Executor(fluid.CPUPlace())
    if how == "for_test_clone":
        with scope_guard(Scope()):
            exe.run(startup)
            exe.run(main.clone(for_test=True), feed=feed, fetch_list=[loss])
            got = str(_step_jaxpr(feed))
    else:
        path = str(tmp_path / "m")
        with scope_guard(Scope()):
            exe.run(startup)
            fluid.io.save_inference_model(path, sorted(feed), [loss], exe,
                                          main_program=main)
        pred = fluid.inference.create_paddle_predictor(
            fluid.inference.AnalysisConfig(model_dir=path))
        with scope_guard(pred._scope):
            pred.run(feed)
            got = str(_step_jaxpr(feed))
    n = CASES[case][2]
    assert _kernels(got)["flash_attention_fwd"] == n
    assert "flash_attention_dq" not in got and "_flash_kept" not in got
    assert got.count("optimization_barrier") == 0
    assert _sites() == {}


def test_a_composite_site_in_a_region_keeps_nothing(monkeypatch):
    """PADDLE_TPU_PALLAS=off: XLA attention in the region, no kernel, so
    nothing to keep, nothing counted, and the region's barrier holds the
    captured inputs and the incoming gradients alone."""
    with_kernel = [len(e.invars) for e in _kept_run("mla")[2].jaxpr.eqns
                   if e.primitive.name == "optimization_barrier"]
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "off")
    with tracing.span("test.root"):     # inside a trace every step records
        losses, _, jaxpr, sites = _train("mla", steps=1)
    assert np.isfinite(np.frombuffer(losses[0], "float32")).all()
    assert not _kernels(jaxpr) and "_flash_kept" not in str(jaxpr)
    assert sites == {}
    composite = [len(e.invars) for e in jaxpr.jaxpr.eqns
                 if e.primitive.name == "optimization_barrier"]
    assert composite == [n - 3 for n in with_kernel]
    attrs = [r["attrs"] for r in tracing.get_tracer().records()
             if r["name"] == "executor.compile"
             and "recompute_kept_bytes" in r["attrs"]][-1]
    assert (attrs["grad_residual_sites_kept_across_region"],
            attrs["recompute_kept_bytes"]) == (0, 0)


def test_a_fused_ln_site_in_a_region_keeps_nothing():
    """Its output is an activation, which is what a region is there to
    drop: each of the four fused-LN sites in BERT's two regions runs its
    forward kernel in the forward pass and in the re-run, and no path of
    the counter names it (the embedding's LN lies outside the regions:
    PR 27's arm, one forward)."""
    _, _, jaxpr, sites = _kept_run("bert_dropout0.1")
    kernels = _kernels(jaxpr)
    assert (kernels["fused_ln_fwd"], kernels["fused_ln_bwd"]) == (9, 5)
    assert sites == {(FLASH, "kept_across_region"): 2,
                     ("fused_dropout_add_ln", "reused"): 1}


def test_compile_span_carries_the_kept_count_and_bytes():
    """``executor.compile`` of the step that traced the block: two sites,
    each ``o`` [B*H, T, d_v] and ``m``, ``l`` [B*H, 1, T] in float32."""
    with tracing.span("test.root"):     # inside a trace every step records
        _train("mla", steps=1)
    attrs = [r["attrs"] for r in tracing.get_tracer().records()
             if r["name"] == "executor.compile"
             and "recompute_kept_bytes" in r["attrs"]][-1]
    heads, dv = 4, 16
    assert (attrs["grad_residual_sites_kept_across_region"],
            attrs["grad_residual_sites_reused"],
            attrs["grad_residual_sites_recomputed"]) == (2, 0, 0)
    assert attrs["recompute_kept_bytes"] == 2 * (
        2 * heads * T * dv * 4 + 2 * 2 * heads * T * 4)


def test_a_site_in_a_loop_inside_a_region_keeps_nothing(monkeypatch):
    """A loop's or a branch's body is a trace of its own: what a site
    inside it computed cannot cross into the grad op's trace, so
    ``ctx.region`` is unset while such a sub-block is lowered; a region
    nested in a region shares the outer one's store."""
    seen = []
    ctx = registry.LoweringContext()
    ctx.region = outer = registry.RegionKept()
    monkeypatch.setattr(
        control_flow, "_run_sub_block_op",
        lambda op, *rest: seen.append((op.type, ctx.region)))
    for kind in ("while", "conditional_block", "recurrent",
                 "recompute_block"):
        control_flow.run_sub_block_op(
            types.SimpleNamespace(type=kind), None, {}, ctx, None)
    assert seen == [("while", None), ("conditional_block", None),
                    ("recurrent", None), ("recompute_block", outer)]
    assert ctx.region is outer
