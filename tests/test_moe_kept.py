"""A recompute region hands the expert layer's plan and its first
block's gate and up products to its backward (``parallel/moe.py``
``held_experts_ffn(residuals=)``, ``ops/nn.py`` ``moe_experts``): the
region's forward keeps them, and the re-run walks the forward's plan and
differentiates that block from them with six grouped products, none of
them forward.  Held against the same program with nothing crossing
(``held_experts_ffn`` given no residuals: every block's gate and up
products computed anew in the backward), on the CPU at ``MELLUM_TINY``
widths; attention stays in XLA (no flash kernel, so nothing else crosses
a region)."""

import collections
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import executor as E
from paddle_tpu.executor import Scope, scope_guard
from paddle_tpu.models import decoder
from jax._src.interpreters import partial_eval as pe

from paddle_tpu.observability import metrics, runtime, tracing
from paddle_tpu.parallel import moe

T, B = 64, 2
CFG = dict(decoder.MELLUM_TINY, num_hidden_layers=2,
           layer_types=["sliding_attention", "full_attention"])
# every choice names a held expert: 256 rows a layer, in blocks of 96
ALL_HELD = dict(CFG, experts_held=CFG["n_routed_experts"])
LOADS = {"one_trip": (CFG, None, 1), "three_trips": (ALL_HELD, 96, 3)}


def _feed():
    ids = np.random.default_rng(0).integers(
        0, CFG["vocab_size"], size=(B, T + 1)).astype("int64")
    return {"input_ids": np.ascontiguousarray(ids[:, :-1]),
            "labels": np.ascontiguousarray(ids[:, 1:])}


def _recomputed_path(monkeypatch):
    """``held_experts_ffn`` with no residuals kept and none taken, as
    where nothing crosses a region: the re-run plans from its own router
    and its backward computes every block's gate and up products anew."""
    whole = moe.held_experts_ffn

    def unkept(*args, residuals=None, return_residuals=False, **kw):
        out = whole(*args, **kw)
        return (out, None) if return_residuals else out

    monkeypatch.setattr(moe, "held_experts_ffn", unkept)


def _step_jaxpr(feed):
    cb = E._LAST_COMPILED_BLOCK
    sc = fluid.global_scope()
    rw = {n: sc.get(n) for n in cb.rw_names}
    ro = {n: sc.get(n) for n in cb.ro_names}
    fv = {n: jnp.asarray(feed[n]) for n in cb.feed_names}
    return cb.jitted.trace(fv, rw, ro, E.rng_key(0)).jaxpr


def _grouped_products(jaxpr, in_loop=False, out=None):
    """``ragged_dot_general`` equations that compute something (the dead
    halves of pullbacks dropped, as XLA drops them) by where they lie:
    ``loop`` (in a ``while``, the trips), ``backward`` (outside one, under
    a transpose) and ``forward``."""
    if out is None:
        out = collections.Counter()
        jaxpr, _ = pe.dce_jaxpr(jaxpr, [True] * len(jaxpr.outvars))
    for e in jaxpr.eqns:
        if e.primitive.name == "ragged_dot_general":
            out["loop" if in_loop else "backward"
                if "transpose(" in str(e.source_info.name_stack)
                else "forward"] += 1
        for sub in jax.core.jaxprs_in_params(e.params):
            _grouped_products(sub, in_loop or e.primitive.name == "while",
                              out)
    return out


def _train(cfg, amp, train, recompute, steps=2):
    """(the losses, the first step's gradient of every parameter by name,
    the step's jaxpr, the compile span's attributes, the trips each
    expert layer's loop made a step, ``grad_residual_sites_total`` by
    ``(op_type, path)``)."""
    metrics.registry().reset()
    cfg = dict(cfg, recompute=recompute)
    with fluid.unique_name.guard():
        main, startup, _, loss = decoder.build_train(
            cfg, seq_len=T, lr=1e-3, amp=amp, train=train)
    main.random_seed = startup.random_seed = 7
    feed = _feed()
    grads = [p.name + "@GRAD" for p in main.all_parameters()
             if p.name + "@GRAD" in main.global_block().vars]
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(Scope()), tracing.span("test.root"):
        exe.run(startup)
        outs = [exe.run(main, feed=feed, fetch_list=[loss] + grads)
                for _ in range(steps)]
        jaxpr = _step_jaxpr(feed)
        counted = runtime.publish_moe_counters()
    block = moe.block_rows(B * T * cfg["num_experts_per_tok"],
                           cfg["experts_held"], cfg["n_routed_experts"])
    attrs = [r["attrs"] for r in tracing.get_tracer().records()
             if r["name"] == "executor.compile"
             and "recompute_kept_bytes" in r["attrs"]][-1]
    return ([float(np.asarray(o[0]).reshape(-1)[0]) for o in outs],
            {n: np.asarray(g, np.float64) for n, g in zip(grads, outs[0][1:])},
            jaxpr, attrs,
            {layer: c["moved"] / block / c["steps"]
             for layer, c in counted.items()},
            {(dict(m.labels)["op_type"], dict(m.labels)["path"]): m.value
             for m in metrics.registry().collect()
             if m.name == "grad_residual_sites_total"})


def _run(load, path, amp=False, train=True, recompute=True):
    """``_train`` at a load of ``LOADS`` with the products crossing the
    region (``kept``) or not (``recomputed``), built once a process."""
    return _run_once(load, path, amp, train, recompute)


@functools.lru_cache(maxsize=None)
def _run_once(load, path, amp, train, recompute):
    cfg, rows, _ = LOADS[load]
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("PADDLE_TPU_PALLAS", "off")
        if rows is not None:
            mp.setattr(moe, "block_rows", lambda *a, **k: rows)
        if path == "recomputed":
            _recomputed_path(mp)
        return _train(cfg, amp, train, recompute)


# load, AMP, the relative L2 allowed between the two paths' gradients:
# the kept h and u are the forward's, the recomputed ones the re-run's,
# which differ by a rounding under AMP
MATCH = {"one_trip_f32": ("one_trip", False, 1e-5),
         "three_trips_f32": ("three_trips", False, 1e-5),
         "one_trip_bf16": ("one_trip", True, 2e-2)}


@pytest.mark.parametrize("case", MATCH)
def test_loss_and_gradients_match_the_recomputed_path(case):
    load, amp, tol = MATCH[case]
    trips = LOADS[load][2]
    kept, anew = _run(load, "kept", amp), _run(load, "recomputed", amp)
    assert kept[5] == {("moe_experts", "kept_across_region"): 2}
    assert anew[5] == {("moe_experts", "recomputed"): 2}
    # the forward does the same arithmetic: the first loss is the same
    assert kept[0][0] == anew[0][0]
    np.testing.assert_allclose(kept[0][1], anew[0][1], rtol=tol)
    assert kept[1].keys() == anew[1].keys() and len(kept[1]) > 10
    moved = {n: np.linalg.norm(kept[1][n] - anew[1][n])
             / np.linalg.norm(anew[1][n]) for n in anew[1]
             if np.linalg.norm(anew[1][n]) > 0}
    assert max(moved.values()) < tol, sorted(moved.items())[-5:]
    assert any(".experts." in n for n in moved)
    assert kept[4] == anew[4] == {"0": trips, "1": trips}


@pytest.mark.parametrize("path,again", [("kept", 0), ("recomputed", 2)])
def test_the_first_trip_of_the_backward_holds_six_grouped_products(
        path, again):
    """Outside the loops, each expert layer's backward runs six grouped
    products (two transposes of each of the first block's three), from
    the kept h and u and none forward; the other products there are the
    forward's three, and the two the backward runs again where h and u are
    computed anew.  (Before the products crossed, the backward ran all of
    its products in the loop, nine a trip with the three forward ones run
    again.)  Dead halves of pullbacks are dropped first, as XLA drops
    them; the backward's own forward products carry no name."""
    products = _grouped_products(_run("one_trip", path)[2].jaxpr)
    assert products["backward"] == 2 * 6
    assert products["forward"] == 2 * (3 + again)
    assert products["loop"] > 0


@pytest.mark.parametrize("how", ["forward_only", "outside_a_region"])
def test_nothing_crosses_where_there_is_no_region_to_cross(how):
    """A forward-only program (its regions have no grad twin) and an
    expert layer outside any region take the plain entry: the step's
    jaxpr is that of the path that keeps nothing, no site is counted, and
    the first block runs ahead of the loop in each pass: three forward
    products a layer, and where there is a backward its six transposes
    and the gate and up products again."""
    kw = (dict(train=False) if how == "forward_only"
          else dict(recompute=False))
    mine, anew = _run("one_trip", "kept", **kw), \
        _run("one_trip", "recomputed", **kw)
    assert mine[5] == {} and mine[3]["recompute_kept_bytes"] == 0
    assert str(mine[2]) == str(anew[2])
    products = _grouped_products(mine[2].jaxpr)
    assert products["loop"] > 0
    assert (products["forward"], products["backward"]) == (
        (2 * 3, 0) if how == "forward_only" else (2 * 5, 2 * 6))


def _kept_bytes(cfg, block):
    """What one expert layer keeps: h and u [block, F] float32 and the
    router's choices [B*T, K] int32."""
    return (2 * block * cfg["moe_intermediate_size"] * 4
            + B * T * cfg["num_experts_per_tok"] * 4)


def test_the_compile_span_counts_the_kept_sites_and_bytes():
    """Two expert layers, each keeping the router's choices and h and u
    of its first block: [1,024 rows (the least block), F 32] float32
    each."""
    attrs = _run("one_trip", "kept")[3]
    assert (attrs["grad_residual_sites_kept_across_region"],
            attrs["grad_residual_sites_recomputed"]) == (2, 0)
    assert attrs["recompute_kept_bytes"] == 2 * _kept_bytes(CFG, 1024) \
        == 2 * (2 * 1024 * 32 * 4 + 128 * 2 * 4)


def _layer(seed, t=512, d=16, f=24, k=2, held=4, total=8):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    idx = jnp.asarray(np.argsort(rng.uniform(size=(t, total)), axis=1)[
        :, :k], jnp.int32)
    gates = jnp.asarray(rng.uniform(0.2, 1.0, size=(t, k)), jnp.float32)
    w = [jnp.asarray(0.3 * rng.normal(size=s), jnp.float32)
         for s in ((held, d, f), (held, d, f), (held, f, d))]
    c = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    return x, idx, gates, w, c


def _other_expert(held_here):
    """A sixteenth of the tokens' first choice moved to an expert that
    the token did not choose: one held here or not."""
    def move(idx):
        out = np.asarray(idx).copy()
        for t in range(0, len(out), 16):
            pool = [e for e in (range(4) if held_here else range(4, 8))
                    if e not in out[t]]
            out[t, 0] = pool[t % len(pool)]
        return jnp.asarray(out), None
    return move


def _reordered(idx):
    """A sixteenth of the tokens' two choices swapped, with their gates:
    the same experts in another order."""
    swap = (np.arange(idx.shape[0]) % 16 == 0)[:, None]
    return jnp.where(swap, idx[:, ::-1], idx), swap


# how the re-run's router chose otherwise than the forward's (a near tie)
RERUN = {
    # the same experts in the other order: only the gates' places move
    "reordered_in_the_top_k": _reordered,
    # another held expert: the same rows, other groups
    "to_another_held_expert": _other_expert(True),
    # an expert held elsewhere: fewer rows here
    "to_an_expert_held_elsewhere": _other_expert(False),
    # a held expert where it was elsewhere, with blocks of 256 rows and
    # the rows in three of them
    "over_three_trips": _other_expert(True),
}


@pytest.mark.parametrize("how", RERUN)
def test_the_rerun_walks_the_forwards_plan(how, monkeypatch):
    """A re-run whose router chose otherwise by a rounding is given the
    forward's residuals: its gradient is the one its forward's choices
    give, row for row (a reordering of a token's choices moves only the
    gates' places), and not what its own plan gives, which would pair the
    kept h and u with other tokens."""
    x, idx, gates, w, c = _layer(3)
    if how == "over_three_trips":
        monkeypatch.setattr(moe, "block_rows", lambda *a, **k: 256)
    rerun_idx, swap = RERUN[how](idx)
    rerun_gates = gates if swap is None else jnp.where(
        swap, gates[:, ::-1], gates)
    assert int((rerun_idx != idx).sum()) > 0

    def grads(idx, gates, residuals=None):
        def loss(x, gates, *w):
            out, _ = moe.held_experts_ffn(x, idx, gates, *w, total=8,
                                          residuals=residuals)
            return jnp.sum(out * c)
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(x, gates, *w)

    (_, rows), kept = moe.held_experts_ffn(x, idx, gates, *w, total=8,
                                           return_residuals=True)
    if how == "over_three_trips":
        assert int(moe.blocks_run(rows, 256)) == 3
    got = list(grads(rerun_idx, rerun_gates, kept))
    want = list(grads(idx, gates))
    if swap is not None:        # the gates' cotangents in the re-run's places
        want[1] = jnp.where(swap, want[1][:, ::-1], want[1])
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    if swap is None:
        own = grads(rerun_idx, rerun_gates)
        assert max(float(jnp.abs(a - o).max())
                   for a, o in zip(got, own)) > 0.1
