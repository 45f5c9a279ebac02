"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once on the attached TPU, through the public entry
points, at the full width of BERT-base (L12 D768 H12 FF3072 V30522,
random weights from a fixed seed), and fails loudly:

    python chip_smoke.py            # one chip: train seq128, train seq512,
                                    # the window's edge, serve
    python chip_smoke.py --chips 4  # four chips: data-parallel vs one chip, only
    python chip_smoke.py --probe window_edge    # that phase alone

Without a TPU it exits non-zero before building anything — there is no CPU
branch and no small model.  Any phase that raises, loses a kernel, or
produces a non-finite or wrong value ends the run non-zero.  Each phase
prints one JSON line; its times are smoke observations on a cold or warm
cache, not benchmark numbers.  The LAST line is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

One process holds the chip(s); nothing is spawned.
"""

import argparse
import copy
import json
import math
import os
import shutil
import sys
import tempfile
import time

import numpy as np

SEED = 0
# serve phase: bf16 predictor vs the f32 program, both on the MXU's bf16
# passes — rounding error accumulated over 12 layers of unit-scale
# (post-LayerNorm) activations.  Seen on a v5e: rel L2 0.0061, max abs
# 0.033; the tolerances leave a factor of five.
SERVE_REL_L2_TOL = 3e-2
SERVE_MAX_ABS_TOL = 0.2
# --chips 4: same weights, same batch, dropout off; what differs is the
# summation order of a bf16 step (per-chip batch 16 vs 64, the all-reduce).
# Two bf16 ulps of the loss; seen on four v5e chips: identical losses.
DP_LOSS_RTOL = 2.0 ** -6


def _emit(phase, **fields):
    print(json.dumps(dict(phase=phase, **fields)), flush=True)


def _fail(msg):
    raise SystemExit("chip_smoke: FAIL: %s" % msg)


def _cache_entries(cache_dir):
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def _compiled_step_text(feed):
    """Compiled HLO of the step the Executor ran last (a persistent-cache
    hit when the cache is on: the same module was compiled a moment ago)."""
    import paddle_tpu as fluid
    from paddle_tpu import executor

    block = executor._LAST_COMPILED_BLOCK
    scope = fluid.global_scope()
    rw = {n: scope.get(n) for n in block.rw_names}
    ro = {n: scope.get(n) for n in block.ro_names}
    feed = {n: feed[n] for n in block.feed_names}
    return block.jitted.lower(
        feed, rw, ro, executor.rng_key(SEED)).compile().as_text()


def _require_kernels(kernels, required, where):
    for name in required:
        if not any(name in k for k in kernels):
            _fail("%s: no %r tpu_custom_call in the compiled step (kernels "
                  "found: %s) — the XLA composite ran instead"
                  % (where, name, dict(kernels) or "none"))


def _pretrain(cfg, seq_len):
    import paddle_tpu as fluid
    from paddle_tpu.models import bert

    with fluid.unique_name.guard():
        main, startup, _, loss = bert.build_pretrain(
            cfg, seq_len=seq_len, lr=1e-4, amp=True, train=True)
    main.random_seed = startup.random_seed = SEED
    return main, startup, loss


def _train_steps(exe, program, feed, fetch_list, steps):
    """``steps`` runs; returns (losses, other fetches of the last step,
    first-step seconds, later-step milliseconds)."""
    losses, ms, last = [], [], None
    for _ in range(steps):
        t0 = time.perf_counter()
        last = exe.run(program, feed=feed, fetch_list=fetch_list,
                       return_numpy=False)
        losses.append(np.asarray(last[0]).item())  # waits for the step
        ms.append((time.perf_counter() - t0) * 1e3)
    if not np.isfinite(losses).all():
        _fail("non-finite loss: %s" % losses)
    return losses, last[1:], ms[0] / 1e3, ms[1:]


def phase_train(name, cfg, seq_len, batch, steps, required_kernels):
    """Masked-LM pretraining through plain ``Executor(TPUPlace())`` with
    the default fusion pipeline: startup, then ``steps`` steps on one
    repeated fake batch."""
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.models import bert
    from paddle_tpu.ops.pallas import pallas_kernels_in
    from paddle_tpu.static_analysis import fusion

    main, startup, loss = _pretrain(cfg, seq_len)
    feed = {k: jnp.asarray(v) for k, v in bert.make_fake_batch(
        batch, seq_len, cfg, np.random.RandomState(SEED)).items()}
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        losses, _, first_s, step_ms = _train_steps(
            exe, main, feed, [loss], steps)
        if not losses[-1] < losses[0]:
            _fail("%s: loss did not fall: %s" % (name, losses))
        t0 = time.perf_counter()
        kernels = pallas_kernels_in(_compiled_step_text(feed))
        hlo_s = time.perf_counter() - t0
    _require_kernels(kernels, required_kernels, name)
    _, report = fusion.resolve_fused_program(main, targets=[loss.name])
    _emit(name, seq_len=seq_len, batch=batch, steps=steps,
          compile_and_first_step_s=round(first_s, 2),
          smoke_step_ms_median=round(float(np.median(step_ms)), 2),
          hlo_recompile_s=round(hlo_s, 2), losses=losses,
          fusion_families=report.counts(), kernels=dict(kernels))
    return losses


def phase_window_edge(t=8192, heads=32, kv_heads=4, d=128, window=1024,
                      keys=(0, 1023, 1024, 4095, 7168), batch=2):
    """Where the flash kernels' band begins and ends, row for row, at the
    shapes of the grouped-query cell (an edge off by one stays inside any
    tolerance on logits; this does not).  ``k = 0``, so every visible key
    weighs ``1 / n_i`` in row i (n_i keys visible); ``v`` is zero but for
    ones at ONE key j0 a (batch, key-value head): row i of the output is
    ``1 / n_i`` where j0 is visible to i and exactly 0 elsewhere, and the
    visible rows must be ``j0 <= i < j0 + window`` (a sliding layer) or
    ``i >= j0`` (a full one).  Backward with ``q_i = dO_i = e_(i mod d)``:
    row i reaches ``dv[j0]`` with ``1 / n_i`` and ``dk[j0]`` with ``(1 /
    n_i)(1 - 1 / n_i) / sqrt(d)``, both in feature ``i mod d``, from each
    query head of the group: a row too many or too few moves one
    feature's sum by an eighth."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import (flash_attention,
                                                       routes_to_kernel)

    group, rows = heads // kv_heads, np.arange(t)
    slots = [(b, h) for b in range(batch) for h in range(kv_heads)]
    if len(keys) > len(slots):
        _fail("window_edge: %d keys for %d (batch, key-value head) slots"
              % (len(keys), len(slots)))
    probes = list(zip(slots, keys))
    code = np.eye(d, dtype="float32")[rows % d]
    q = jnp.asarray(np.broadcast_to(code, (batch, heads, t, d)),
                    jnp.bfloat16)
    k = jnp.zeros((batch, kv_heads, t, d), jnp.bfloat16)
    v = np.zeros((batch, kv_heads, t, d), "float32")
    for (b, h), j0 in probes:
        v[b, h, j0] = 1.0
    v = jnp.asarray(v, jnp.bfloat16)
    if not routes_to_kernel(q, k, None, v):
        _fail("window_edge: these shapes do not route to the flash kernels")
    worst = {}
    for kind, w in (("sliding", window), ("full", None)):
        out, pullback = jax.vjp(lambda q, k, v, w=w: flash_attention(
            q, k, v, causal=True, window=w), q, k, v)
        _, dk, dv = pullback(q)
        out, dk, dv = (np.asarray(x, "float64") for x in (out, dk, dv))
        n = np.minimum(rows + 1, w or t)
        for (b, h), j0 in probes:
            seen = (rows >= j0) & (rows - j0 < (w or t))
            p = np.where(seen, 1.0 / n, 0.0)
            for head in range(h * group, (h + 1) * group):
                got = out[b, head]
                wrong = np.flatnonzero(((got != 0).any(axis=1)) != seen)
                if wrong.size:
                    _fail("window_edge: %s layer, key %d, head %d: rows %s "
                          "%s it" % (kind, j0, head, wrong[:8].tolist(),
                                     "do not see" if seen[wrong[0]]
                                     else "see"))
                off = np.abs(got[seen] / p[seen, None] - 1).max()
                worst[kind] = max(worst.get(kind, 0.0), float(off))
            for name, got, weight in (
                    ("dv", dv[b, h, j0], p),
                    ("dk", dk[b, h, j0], p * (1 - p) / math.sqrt(d))):
                want = group * np.bincount(rows % d, weights=weight,
                                           minlength=d)
                off = np.abs(got - want).max() / np.abs(want).max()
                worst[kind + "_" + name] = max(
                    worst.get(kind + "_" + name, 0.0), float(off))
    # bfloat16 rounds 1 / n_i by 2^-9; a row more or less is 1 in 8
    if max(worst.values()) > 2e-2:
        _fail("window_edge: values off by %s" % worst)
    _emit("window_edge", seq_len=t, heads=heads, kv_heads=kv_heads,
          window=window, keys=list(keys), rows="as stated, every head",
          worst_relative=worst)
    return worst


def _encoder_program(cfg, seq_len):
    import paddle_tpu as fluid
    from paddle_tpu.models import bert

    cfg = copy.copy(cfg)
    cfg.dropout = cfg.attn_dropout = 0.0
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        ids = fluid.layers.data("input_ids", shape=[seq_len], dtype="int64")
        types = fluid.layers.data("token_type_ids", shape=[seq_len],
                                  dtype="int64")
        mask = fluid.layers.data("attn_mask_bias", shape=[1, 1, seq_len],
                                 dtype="float32")
        hidden = bert.encoder(ids, types, mask, cfg, seq_len)
    startup.random_seed = SEED
    return main, startup, hidden


def phase_serve(cfg, seq_len, request_rows, buckets,
                rel_l2_tol=SERVE_REL_L2_TOL, max_abs_tol=SERVE_MAX_ABS_TOL):
    """The encoder exported with ``save_inference_model``, loaded through
    the bf16 ``AnalysisPredictor``, served by ``PredictorServer``; every
    answer is compared with the same rows run through ``Executor`` on the
    ``clone(for_test=True)`` program (one batch of all rows, so one
    compile)."""
    import paddle_tpu as fluid
    from paddle_tpu import serving
    from paddle_tpu.models import bert
    from paddle_tpu.ops.pallas import pallas_kernels_in

    feed_names = ["input_ids", "token_type_ids", "attn_mask_bias", "pos_ids"]
    main, startup, hidden = _encoder_program(cfg, seq_len)
    total = sum(request_rows)
    rows = bert.make_fake_batch(total, seq_len, cfg,
                                np.random.RandomState(SEED), max_pred=0)
    rows = {n: rows[n] for n in feed_names}
    # every other row is padded after 3/4 of the sequence
    rows["attn_mask_bias"][::2, ..., seq_len * 3 // 4:] = -1e4
    export_dir = tempfile.mkdtemp(prefix="chip_smoke_export_")
    try:
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.TPUPlace())
            exe.run(startup)
            fluid.io.save_inference_model(export_dir, feed_names, [hidden],
                                          exe, main_program=main)
            want = np.asarray(exe.run(main.clone(for_test=True), feed=rows,
                                      fetch_list=[hidden])[0], "float32")
        config = fluid.inference.AnalysisConfig(model_dir=export_dir)
        config.enable_bf16()
        predictor = fluid.inference.create_paddle_predictor(config)
    finally:
        shutil.rmtree(export_dir, ignore_errors=True)

    offsets = np.concatenate([[0], np.cumsum(request_rows)])
    feeds = [{n: v[a:b] for n, v in rows.items()}
             for a, b in zip(offsets[:-1], offsets[1:])]
    half = len(feeds) // 2
    with serving.PredictorServer({"bert": predictor},
                                 buckets=buckets) as server:
        t0 = time.perf_counter()
        server.warmup({"bert": {n: v[:1] for n, v in rows.items()}})
        warmup_s = time.perf_counter() - t0
        answers, request_ms = [], []
        for feed in feeds[:half]:       # one client, one request at a time
            t0 = time.perf_counter()
            answers.append(server.submit("bert", feed).result(timeout=300))
            request_ms.append((time.perf_counter() - t0) * 1e3)
        burst = [server.submit("bert", feed) for feed in feeds[half:]]
        answers += [r.result(timeout=300) for r in burst]
        stats = server.stats()
        used = sorted({bucket for _, bucket, _ in server.dispatch_log})
    with fluid.scope_guard(predictor._scope):
        # warmup compiled the buckets in order, the largest last
        kernels = pallas_kernels_in(_compiled_step_text(
            server.buckets.pad_feed(feeds[-1], request_rows[-1],
                                    server.buckets.sizes[-1])))

    if stats["completed"] != len(feeds) or stats["failed"] or stats["shed"]:
        _fail("serve: not every request was answered: %s" % stats)
    if len(used) < 2:
        _fail("serve: requests used buckets %s, wanted at least two" % used)
    rel_l2 = max_abs = 0.0
    for a, b, (got,) in zip(offsets[:-1], offsets[1:], answers):
        got = np.asarray(got, "float32")
        if got.shape != want[a:b].shape or not np.isfinite(got).all():
            _fail("serve: bad answer for rows %d:%d (shape %s)"
                  % (a, b, got.shape))
        diff = got - want[a:b]
        rel_l2 = max(rel_l2, float(np.linalg.norm(diff)
                                   / np.linalg.norm(want[a:b])))
        max_abs = max(max_abs, float(np.abs(diff).max()))
    if rel_l2 > rel_l2_tol or max_abs > max_abs_tol:
        _fail("serve: bf16 predictor vs Executor: rel L2 %.4g (tol %.3g), "
              "max abs %.4g (tol %.3g)"
              % (rel_l2, rel_l2_tol, max_abs, max_abs_tol))
    _emit("serve", seq_len=seq_len, request_rows=list(request_rows),
          buckets_used=used, dispatches=stats["dispatches"],
          warmup_compile_s=round(warmup_s, 2),
          smoke_request_ms_median=round(float(np.median(request_ms)), 2),
          rel_l2_vs_executor=rel_l2, max_abs_vs_executor=max_abs,
          checksum=float(np.abs(want).sum()), kernels=dict(kernels))


def phase_data_parallel(cfg, seq_len, batch, steps, rtol=DP_LOSS_RTOL):
    """The same training run on one device through ``Executor`` and on
    every device through ``CompiledProgram.with_data_parallel``: losses
    agree step by step, batch and gradients span all devices, and the
    compiled step holds an all-reduce."""
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.models import bert
    from paddle_tpu.ops.pallas import pallas_kernels_in

    devices = jax.devices()
    feed = bert.make_fake_batch(batch, seq_len, cfg,
                                np.random.RandomState(SEED))
    grad = "bert.pos_emb@GRAD"

    def run(parallel):
        main, startup, loss = _pretrain(cfg, seq_len)
        program = main
        if parallel:
            program = fluid.CompiledProgram(main).with_data_parallel(
                loss_name=loss.name)
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.TPUPlace())
            exe.run(startup)
            losses, (fed, g), first_s, step_ms = _train_steps(
                exe, program, feed, [loss, "input_ids", grad], steps)
            text = _compiled_step_text(feed)
            spans = {
                "batch": len(fed.device_value.sharding.device_set),
                "gradient": len(g.device_value.sharding.device_set),
                "parameter": len(fluid.global_scope().get(
                    "bert.pos_emb").sharding.device_set),
            }
            # the runtime's own count (the CPU backend reports none)
            in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                      for d in devices]
        return dict(losses=losses, spans=spans, bytes_in_use=in_use,
                    all_reduces=text.count(" all-reduce"),
                    kernels=dict(pallas_kernels_in(text)),
                    compile_and_first_step_s=round(first_s, 2),
                    smoke_step_ms_median=round(float(np.median(step_ms)), 2))

    one, many = run(False), run(True)
    _emit("data_parallel.one_chip", **one)
    _emit("data_parallel.all_chips", devices=len(devices), **many)
    worst = max(abs(a - b) / abs(a)
                for a, b in zip(one["losses"], many["losses"]))
    if worst > rtol:
        _fail("data parallel: losses differ by %.3g relative (tol %.3g): "
              "%s vs %s" % (worst, rtol, one["losses"], many["losses"]))
    if set(many["spans"].values()) != {len(devices)}:
        _fail("data parallel: arrays do not span %d devices: %s"
              % (len(devices), many["spans"]))
    if any(b == 0 for b in many["bytes_in_use"]):
        _fail("data parallel: a device holds no data: %s"
              % many["bytes_in_use"])
    if not many["all_reduces"]:
        _fail("data parallel: no all-reduce in the compiled step")
    _emit("data_parallel", steps=steps, batch=batch,
          loss_rel_diff_max=worst, rtol=rtol)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data-parallel path and the "
                         "one-chip run it is compared with")
    ap.add_argument("--probe", choices=("window_edge",),
                    help="run only this phase (one chip)")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            "chip_smoke: no TPU: jax.devices()[0].platform is %r. This "
            "script only runs on the chip (send it through the chip tool);"
            " tier-1 tests are the CPU check." % devices[0].platform)
    if len(devices) != args.chips:
        raise SystemExit("chip_smoke: --chips %d but JAX reports %d devices"
                         % (args.chips, len(devices)))

    from paddle_tpu import autotune, native
    from paddle_tpu.core import configure_compile_cache
    from paddle_tpu.models import bert

    cache_dir = configure_compile_cache()
    tuned = autotune.cache_path()
    built = native.is_native()
    _emit("setup", jax=jax.__version__, compile_cache_dir=cache_dir,
          compile_cache_entries=_cache_entries(cache_dir),
          autotune_cache=tuned, autotune_cache_exists=os.path.exists(tuned),
          native_library_built=built)
    if not built:
        _fail("paddle_tpu/native did not build from its sources here")

    base = copy.copy(bert.BERT_BASE)
    base.fused_ln = True
    if args.probe:
        phase_window_edge()
    elif args.chips == 1:
        # the shipped flagship graph (bench.py child_bert): fused QKV at
        # seq128 only, fused LN, fuse_attn="auto", masked-gather head
        flagship = copy.copy(base)
        flagship.fused_qkv = True
        phase_train("train_seq128", flagship, 128, 64, 8,
                    ("fused_ln_fwd", "fused_ln_bwd"))
        phase_train("train_seq512", base, 512, 16, 4,
                    ("fused_ln_fwd", "fused_ln_bwd", "flash_attention_fwd",
                     "flash_attention_dkv", "flash_attention_dq"))
        phase_window_edge()
        phase_serve(bert.BERT_BASE, 128, (1, 3, 2, 4, 1, 1, 2),
                    buckets=(2, 4))
    else:
        base.fused_qkv = True
        base.dropout = base.attn_dropout = 0.0
        phase_data_parallel(base, 128, 64, 6)

    _emit("done", compile_cache_entries=_cache_entries(cache_dir))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
