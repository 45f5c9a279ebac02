"""One forward kernel per Mosaic kernel site (executor._run_ops_into_env,
ops/registry.py KeptForward): where the forward op of a fused-LN or flash
site routes to its Pallas kernel and its grad twin is lowered in the same
call, the grad op takes the forward op's residuals instead of running the
forward kernel again under ``jax.vjp``.  CPU, kernels in interpret mode
with the debug hash mask (``pltpu`` PRNG has no CPU lowering)."""

import collections
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import executor as E
from paddle_tpu.executor import Scope, scope_guard
from paddle_tpu.observability import metrics
from paddle_tpu.ops import registry

N_SITES = 3
B, T, D, H = 2, 128, 128, 2
FWD = {"ln": ["fused_ln_fwd"], "flash": ["flash_attention_fwd"]}
BWD = {"ln": ["fused_ln_bwd"],
       "flash": ["flash_attention_dkv", "flash_attention_dq"]}
OP_TYPE = {"ln": "fused_dropout_add_ln", "flash": "fused_multihead_attention"}
CASES = [("ln", 0.0), ("ln", 0.1), ("flash", 0.0), ("flash", 0.1)]


@pytest.fixture(autouse=True)
def _interpret_debug_env(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
    monkeypatch.setenv("PADDLE_TPU_FLASH_DROPOUT_DEBUG", "iota")
    metrics.registry().reset()


def _build(kind, rate, train=True):
    """N_SITES sites of one fused op between fc layers, a mean loss, SGD."""
    fluid.unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[T, D], dtype="float32")
        h = x
        for _ in range(N_SITES):
            y = fluid.layers.fc(h, size=D, num_flatten_dims=2, act="tanh")
            if kind == "ln":
                h = fluid.layers.fused_dropout_add_ln(y, h,
                                                      dropout_prob=rate)
            else:
                q = fluid.layers.transpose(
                    fluid.layers.reshape(y, [0, T, H, D // H]), [0, 2, 1, 3])
                a = fluid.layers.fused_multihead_attention(
                    q, q, q, dropout_rate=rate)
                h = fluid.layers.reshape(
                    fluid.layers.transpose(a, [0, 2, 1, 3]), [0, T, D]) + h
        loss = fluid.layers.mean(fluid.layers.square(h))
        test = main.clone(for_test=True)
        if train:
            fluid.optimizer.SGD(0.05).minimize(loss)
    return main, startup, loss, test


def _feed():
    return {"x": np.random.RandomState(3).randn(B, T, D).astype("float32")}


def _step_jaxpr(feed):
    """The jaxpr of the step the Executor compiled last."""
    cb = E._LAST_COMPILED_BLOCK
    sc = fluid.global_scope()
    rw = {n: sc.get(n) for n in cb.rw_names}
    ro = {n: sc.get(n) for n in cb.ro_names}
    fv = {n: jnp.asarray(feed[n]) for n in cb.feed_names}
    return str(cb.jitted.trace(fv, rw, ro, E.rng_key(0)).jaxpr)


def _kernels(jaxpr_text):
    """pallas_call equations of a jaxpr by kernel name."""
    return collections.Counter(
        re.findall(r"\bname=((?:fused_ln|flash_attention)_\w+)", jaxpr_text))


def _sites():
    return {(dict(m.labels)["op_type"], dict(m.labels)["path"]): m.value
            for m in metrics.registry().collect()
            if m.name == "grad_residual_sites_total"}


def _train(kind, rate, steps=3, program=None, fetch_grads=False):
    """``steps`` steps; returns (losses as bytes, the step's jaxpr, the
    counter, the first step's parameter gradients)."""
    main, startup, loss, _ = _build(kind, rate)
    grads = [p.name + "@GRAD" for p in main.all_parameters()] \
        if fetch_grads else []
    feed = _feed()
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(Scope()):
        exe.run(startup)
        losses, first = [], None
        for _ in range(steps):
            out = exe.run(program(main, loss) if program else main,
                          feed=feed, fetch_list=[loss] + grads)
            losses.append(np.asarray(out[0]).tobytes())
            first = first if first is not None else \
                [np.asarray(g) for g in out[1:]]
        return losses, _step_jaxpr(feed), _sites(), first


def _split_lowering(monkeypatch):
    """Forces the generic arm: the forward ops and the rest of every op
    list are lowered in two ``_run_ops_into_env`` calls."""
    whole = E._run_ops_into_env

    def in_two_calls(block, env, ctx, ops=None):
        ops = list(block.ops if ops is None else ops)
        cut = next((i for i, op in enumerate(ops)
                    if op.type.endswith("_grad")), len(ops))
        whole(block, env, ctx, ops=ops[:cut])
        return whole(block, env, ctx, ops=ops[cut:])

    monkeypatch.setattr(E, "_run_ops_into_env", in_two_calls)


@pytest.mark.parametrize("kind,rate", CASES)
def test_one_forward_kernel_per_site(kind, rate):
    """N forward + N backward kernels in the step (2N + N before), and
    the counter says every site reused the forward's residuals."""
    _, jaxpr, sites, _ = _train(kind, rate, steps=1)
    kernels = _kernels(jaxpr)
    assert kernels == {k: N_SITES for k in FWD[kind] + BWD[kind]}, kernels
    assert sites == {(OP_TYPE[kind], "reused"): N_SITES}, sites


@pytest.mark.parametrize("kind", ["ln", "flash"])
def test_composite_sites_are_no_kernel_sites(kind, monkeypatch):
    """PADDLE_TPU_PALLAS=off: no kernel in the step, nothing reused (XLA
    merges the composites' recompute; the lowering is the generic one)."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "off")
    losses, jaxpr, sites, _ = _train(kind, 0.1, steps=1)
    assert not _kernels(jaxpr)
    assert sites == {}
    assert "custom_vjp_call" not in jaxpr
    assert np.isfinite(np.frombuffer(losses[0], "float32")).all()


@pytest.mark.parametrize("kind,rate", CASES)
def test_reused_arm_bit_equal_to_generic_arm(kind, rate, monkeypatch):
    """Same kernels on the same inputs: parameter gradients of the first
    step and three steps of losses are bit-equal between the two arms."""
    reused = _train(kind, rate, fetch_grads=True)
    metrics.registry().reset()
    _split_lowering(monkeypatch)
    generic = _train(kind, rate, fetch_grads=True)
    # the generic arm runs every forward kernel a second time
    want = {k: 2 * N_SITES for k in FWD[kind]}
    want.update({k: N_SITES for k in BWD[kind]})
    assert _kernels(generic[1]) == want
    assert generic[2] == {(OP_TYPE[kind], "recomputed"): N_SITES}
    assert reused[0] == generic[0]
    assert len(reused[3]) == len(generic[3]) > 0
    for a, b in zip(reused[3], generic[3]):
        assert a.tobytes() == b.tobytes()


def _plain_lowering(block, env, ctx, ops=None):
    """The loop of ``_run_ops_into_env`` before any op could keep its
    vjp: every op through ``call_op``, nothing else."""
    for i, op in enumerate(block.ops if ops is None else ops):
        if op.type in ("feed", "fetch"):
            continue
        ins = {slot: [env.get(n) for n in names]
               for slot, names in op.inputs.items()}
        with jax.named_scope("pd%d_%s" % (i, op.type)):
            outs = registry.call_op(
                registry.get_op_def(op.type), ctx, ins, op.attrs,
                op_id=op.attrs.get("__op_id__", 0))
        for slot, names in op.outputs.items():
            for n, v in zip(names, outs.get(slot) or []):
                env[n] = v
    return env


@pytest.mark.parametrize("how", ["for_test_clone", "predictor_export"])
@pytest.mark.parametrize("kind", ["ln", "flash"])
def test_forward_only_programs_lower_as_before(kind, how, tmp_path,
                                               monkeypatch):
    """No backward in the op list: the kernel's forward is lowered plainly
    (``custom_vjp_call`` still in the jaxpr, once a site), the jaxpr the
    same text as the plain loop's, and no site is counted."""
    main, startup, loss, test = _build(kind, 0.1, train=False)
    feed = _feed()
    exe = fluid.Executor(fluid.CPUPlace())

    def run():
        if how == "for_test_clone":
            with scope_guard(Scope()):
                exe.run(startup)
                exe.run(test, feed=feed, fetch_list=[loss])
                return _step_jaxpr(feed)
        path = str(tmp_path / "m")
        with scope_guard(Scope()):
            exe.run(startup)
            fluid.io.save_inference_model(path, ["x"], [loss], exe,
                                          main_program=main)
        pred = fluid.inference.create_paddle_predictor(
            fluid.inference.AnalysisConfig(model_dir=path))
        with scope_guard(pred._scope):
            pred.run(feed)
            return _step_jaxpr(feed)

    got = run()
    assert got.count("custom_vjp_call") == N_SITES
    assert _kernels(got) == {k: N_SITES for k in FWD[kind]}
    assert _sites() == {}
    monkeypatch.setattr(E, "_run_ops_into_env", _plain_lowering)
    assert run() == got


@pytest.mark.parametrize("kind", ["ln", "flash"])
def test_overwritten_input_falls_back(kind):
    """A forward input's name rewritten between the forward op and its
    grad: the grad op is not fed what the forward saw, so it re-derives
    the forward from what it is fed, as before."""
    main, startup, loss, _ = _build(kind, 0.1)
    block = main.global_block()
    fwd_idx = [i for i, op in enumerate(block.ops)
               if op.type == OP_TYPE[kind]][-1]
    slot = "X" if kind == "ln" else "Q"
    name = block.ops[fwd_idx].inputs[slot][0]
    block._insert_op(fwd_idx + 1, type="scale", inputs={"X": [name]},
                     outputs={"Out": [name]}, attrs={"scale": 1.0})
    feed = _feed()
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(Scope()):
        exe.run(startup)
        (lv,) = exe.run(main, feed=feed, fetch_list=[loss])
        kernels = _kernels(_step_jaxpr(feed))
    assert np.isfinite(np.asarray(lv)).all()
    assert _sites() == {(OP_TYPE[kind], "reused"): N_SITES - 1,
                        (OP_TYPE[kind], "recomputed"): 1}
    assert kernels == dict(
        {k: N_SITES + 1 for k in FWD[kind]},
        **{k: N_SITES for k in BWD[kind]})


def _accumulating(main, loss):
    bs = fluid.BuildStrategy()
    bs.batch_merge_repeat = 2
    return fluid.CompiledProgram(main, build_strategy=bs)


@pytest.mark.parametrize("kind", ["ln", "flash"])
def test_reuse_under_the_accumulation_split(kind, monkeypatch):
    """Gradient accumulation lowers forward + backward (the head) inside a
    scan and the optimizer ops (the tail) outside: the kept residuals live
    and die inside the head's call, so no tracer leaks, and the steps'
    losses are those of the generic arm, bit for bit."""
    progs = {}

    def program(main, loss):
        return progs.setdefault(id(main), _accumulating(main, loss))

    losses, _, sites, _ = _train(kind, 0.1, steps=3, program=program)
    assert np.isfinite([np.frombuffer(x, "float32")[0]
                        for x in losses]).all()
    assert len(set(losses)) == 3        # the optimizer ops ran
    assert sites == {(OP_TYPE[kind], "reused"): N_SITES}
    metrics.registry().reset()
    _split_lowering(monkeypatch)
    generic, _, sites, _ = _train(kind, 0.1, steps=3, program=program)
    assert sites == {(OP_TYPE[kind], "recomputed"): N_SITES}
    assert losses == generic


def test_fused_sites_inside_recompute_block_train():
    """Inside ``recompute_block`` the forward is lowered by the sub-block's
    own call (no grad twin there) and the backward by a vjp over the whole
    block run again: neither arm of ``_run_ops_into_env`` applies.  A
    flash site keeps its forward kernel's ``o, m, l`` across its region
    (one forward kernel a site, ``tests/test_recompute_kept.py``); a
    fused-LN site's output is an activation, which a region is there to
    drop, so its kernel runs in the forward pass and in the re-run and no
    path names it; and BERT with both under recompute still trains."""
    from paddle_tpu.models import bert

    fluid.unique_name.switch()
    cfg = bert.BertConfig(vocab_size=128, hidden=128, layers=2, heads=2,
                          ffn=256, max_seq=128, dropout=0.1, fused_ln=True,
                          fuse_attn=True, recompute=True)
    main, startup, _, loss = bert.build_pretrain(cfg, seq_len=128, lr=1e-3,
                                                 train=True)
    feed = bert.make_fake_batch(2, 128, cfg, np.random.RandomState(2))
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(Scope()):
        exe.run(startup)
        vals = [float(np.asarray(exe.run(main, feed=feed,
                                         fetch_list=[loss])[0]).reshape(-1)[0])
                for _ in range(4)]
        kernels = _kernels(_step_jaxpr(feed))
    assert np.isfinite(vals).all() and vals[-1] < vals[0]
    assert (kernels["flash_attention_fwd"], kernels["flash_attention_dkv"],
            kernels["flash_attention_dq"]) == (2, 2, 2)
    # four LN sites in the two regions twice, the embedding's once
    assert (kernels["fused_ln_fwd"], kernels["fused_ln_bwd"]) == (9, 5)
    # the embedding LN sits outside the recompute blocks: one plain site
    assert _sites() == {
        ("fused_multihead_attention", "kept_across_region"): 2,
        ("fused_dropout_add_ln", "reused"): 1}


def test_compile_span_carries_the_two_counts():
    """The ``compile`` phase of the step that traced the block holds the
    three counts as attributes, and the bytes kept across regions (none
    here: no region)."""
    from paddle_tpu.observability import tracing

    with tracing.span("test.root"):     # inside a trace every step records
        _train("ln", 0.0, steps=1)
    spans = [r for r in tracing.get_tracer().records()
             if r["name"] == "executor.compile"
             and "grad_residual_sites_reused" in r["attrs"]]
    assert [(s["attrs"]["grad_residual_sites_reused"],
             s["attrs"]["grad_residual_sites_recomputed"],
             s["attrs"]["grad_residual_sites_kept_across_region"],
             s["attrs"]["recompute_kept_bytes"])
            for s in spans][-1] == (N_SITES, 0, 0, 0)
