"""Flagship benchmarks: BERT-base MLM training (tokens/sec/chip + MFU,
the headline metric, printed LAST) and ResNet-50 ImageNet-shape training
(images/sec/chip + MFU, BASELINE.json's first north star), plus a
seq512 BERT line exercising the Pallas flash-attention kernel.

Reference harness analogue: ``benchmark/fluid/fluid_benchmark.py:296-300``
(same examples/sec methodology: timed steps after warmup) +
``benchmark/fluid/models/resnet.py``.  Target from BASELINE.json: >=45%
MFU on a v5e chip (bf16 peak 197 TFLOP/s).

One process per chip: the orchestrator (``main``) imports NO jax, so it
never holds the chip; every workload runs in its own child process, one
after another, each with a hard timeout.  The chip children (``bert``,
``bert<T>``, ``resnet``, ``infer``, ``bert_infer``, ``ctr``) refuse to
run without a TPU, and ``main`` exits non-zero when the probe finds no
chip or any child fails — no metric is ever printed from a CPU run.

Prints one JSON line per workload (flagship BERT seq128 line last):
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}
"""

import json
import os
import subprocess
import sys
import time

import numpy as np


V5E_BF16_PEAK = 197e12  # TPU v5e per-chip bf16 peak FLOP/s

FLAGSHIP_METRIC = "bert_base_mlm_train_tokens_per_sec_per_chip"

PROBE_TIMEOUT_S = 120


def model_train_flops_per_token(cfg, seq_len, max_pred=None):
    """Analytic FLOPs per token for one fwd+bwd step (bwd = 2x fwd).
    max_pred: the MLM head scores only that many gathered positions per
    sequence (models/bert.py default), so the vocab-projection term
    scales by max_pred/seq_len — the MFU denominator must count the
    FLOPs the model actually runs, not the legacy all-position head."""
    d, ff, layers, vocab = cfg.hidden, cfg.ffn, cfg.layers, cfg.vocab_size
    if max_pred is None:
        # lazy: only children import the model package (orchestrator
        # stays jax-free)
        from paddle_tpu.models.bert import default_max_pred

        max_pred = default_max_pred(seq_len)
    head_frac = (max_pred / seq_len) if max_pred else 1.0
    per_layer = (
        2 * 4 * d * d          # q,k,v,o projections
        + 2 * 2 * d * ff       # ffn in+out
        + 2 * 2 * seq_len * d  # scores + context matmuls
    )
    # MLM vocab projection over the gathered masked positions only
    fwd = layers * per_layer + 2 * d * vocab * head_frac
    return 3 * fwd


def peak_flops(device):
    """bf16 peak FLOP/s by ``device_kind``; an unknown device is an
    error, never a default (an MFU against a guessed peak is noise)."""
    kind = device.device_kind.lower()
    if "v5 lite" in kind or "v5e" in kind:
        return V5E_BF16_PEAK
    if "v4" in kind:
        return 275e12
    raise ValueError("no bf16 peak known for device_kind %r"
                     % device.device_kind)


# fwd = 4.09 GMACs @224^2 (the standard torchvision/fvcore count, which
# counts multiply-accumulates) = 8.18 GFLOP; train = 3x fwd (bwd = 2x).
# The first r05 hardware capture's MFU cross-check caught this constant
# treating MACs as FLOPs (analytic 0.101 vs xla 0.308).  The residual
# analytic-vs-xla gap after the fix is real: XLA's cost model counts the
# padding/dilation zeros the MXU physically multiplies in stride-2
# backward convs (hardware FLOPs > model FLOPs), so for conv nets
# mfu_xla is expected ~1.5x mfu_analytic; MFU reports the model count.
RESNET50_TRAIN_FLOPS_PER_IMAGE = 3 * 2 * 4.09e9


def _require_chip():
    """The device of a chip child — or no child: a measurement path that
    finds no chip fails, it does not fall back to the CPU."""
    from paddle_tpu.core import require_tpu

    return require_tpu("bench.py: this child")


def _child_setup():
    """Per-child set-up, before the first compile: the persistent compile
    cache (``core.configure_compile_cache``: left to JAX where
    JAX_COMPILATION_CACHE_DIR is set, else ``<checkout>/.jax_cache``)."""
    from paddle_tpu.core import configure_compile_cache

    configure_compile_cache()


# ---------------------------------------------------------------------------
# child workloads (each runs in its own subprocess; may import jax)
# ---------------------------------------------------------------------------


def child_probe():
    """Initialize the backend and report platform/device kind as JSON."""
    import jax

    dev = jax.devices()[0]
    # one tiny computation proves the backend executes, not just
    # enumerates
    import jax.numpy as jnp

    x = jnp.ones((8, 8))
    float((x @ x).sum())
    print(json.dumps({
        "probe": "ok",
        "platform": str(dev.platform),
        "device_kind": getattr(dev, "device_kind", str(dev)),
        "n_devices": len(jax.devices()),
    }), flush=True)


def _timed_steps(exe, main_prog, feed, loss, warmup, steps):
    """Shared measured-throughput discipline (fluid_benchmark.py:296-300):
    warmup, then a synchronizing loss fetch (async dispatch must not bill
    compile/warmup tails to the window — and a NaN fails BEFORE timing),
    then `steps` runs whose last one fetches the loss to close the
    window.  Returns wall seconds for the `steps` runs."""
    for _ in range(warmup):
        exe.run(main_prog, feed=feed, fetch_list=[])
    lv = exe.run(main_prog, feed=feed, fetch_list=[loss])[0]  # sync
    assert np.isfinite(lv).all()
    t0 = time.perf_counter()
    for _ in range(steps - 1):
        exe.run(main_prog, feed=feed, fetch_list=[])
    lv = exe.run(main_prog, feed=feed, fetch_list=[loss])[0]  # final sync
    dt = time.perf_counter() - t0
    assert np.isfinite(lv).all()
    return dt


def _xla_flops_per_step(scope, feed):
    """XLA's OWN cost-model FLOPs for the compiled step — the
    independent cross-check of the analytic MFU denominator (VERDICT r4
    weak #6: a FLOPs-counting bug would otherwise silently inflate every
    MFU claim).  Returns FLOPs per single optimizer step, or None when
    the backend can't report it.  AOT-lowers the SAME jitted callable
    the timed loop ran, so with the persistent compile cache this is a
    cache hit, not a fresh compile."""
    if os.environ.get("PADDLE_BENCH_MFU_XCHECK", "1") == "0":
        return None
    try:
        import paddle_tpu.executor as ex

        cb = ex._LAST_COMPILED_BLOCK
        if cb is None:
            return None
        rw = {n: scope.get(n) for n in cb.rw_names}
        ro = {n: scope.get(n) for n in cb.ro_names}
        comp = cb.jitted.lower(feed, rw, ro, ex.rng_key(0)).compile()
        ca = comp.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        flops = float(ca.get("flops", 0.0))
        if flops <= 0:
            return None
        # XLA's cost analysis counts a while/scan body ONCE regardless
        # of trip count (verified: a length-4 scan of a matmul reports
        # the same flops as the unscanned matmul; the r05 ipr25
        # hardware capture read 25x low under the old /iters division),
        # so the reported figure already IS per-step for the
        # num_iteration_per_run scan wrapper.
        return flops
    except Exception as e:  # noqa: BLE001 - cross-check is best-effort
        print("# mfu cross-check unavailable: %s" % str(e)[-200:],
              flush=True)
        return None


def _mfu_fields(mfu_analytic, steps_per_sec, xla_flops, peak,
                warn=True, band=(0.90, 1.10)):
    """Extra JSON fields carrying both MFU accountings; flags
    disagreement when mfu_xla falls outside ``band`` × mfu_analytic
    (drivers read metric/value/unit, extra keys ride along).
    warn=False for the CPU smoke models, whose analytic count
    deliberately omits vector-op FLOPs that only matter at tiny scale —
    the fields still record both numbers, the loud audit line fires only
    for the real benchmark models.  Conv nets pass a wider band: XLA's
    cost model counts the padding/dilation zeros the MXU physically
    multiplies in stride-2 backward convs, so hardware FLOPs run
    ~1.5x the model count there by design, not by bug."""
    fields = {"mfu_analytic": round(mfu_analytic, 4)}
    if xla_flops:
        mfu_xla = steps_per_sec * xla_flops / peak
        fields["mfu_xla"] = round(mfu_xla, 4)
        ratio = mfu_xla / mfu_analytic if mfu_analytic > 0 else 1.0
        if not band[0] <= ratio <= band[1]:
            fields["mfu_disagree"] = True
            if warn:
                print("# MFU CROSS-CHECK DISAGREEMENT: analytic %.4f vs "
                      "xla-cost-model %.4f (ratio %.2f outside [%.2f, "
                      "%.2f]) — audit the FLOPs count"
                      % (mfu_analytic, mfu_xla, ratio, band[0], band[1]),
                      flush=True)
    return fields


def _wrap_iters_per_run(main_prog, loss, steps):
    """Shared K-steps-per-dispatch knob (PADDLE_BENCH_ITERS_PER_RUN):
    returns (run_prog, adjusted_dispatch_count, iters)."""
    import jax

    import paddle_tpu as fluid

    iters = max(1, int(os.environ.get("PADDLE_BENCH_ITERS_PER_RUN", "1")
                       or 1))
    if iters <= 1:
        return main_prog, steps, 1
    es = fluid.ExecutionStrategy()
    es.num_iteration_per_run = iters
    run_prog = fluid.CompiledProgram(main_prog).with_data_parallel(
        loss_name=loss.name, exec_strategy=es, places=jax.devices()[:1])
    return run_prog, max(1, steps // iters), iters


def child_resnet():
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.models import resnet
    from paddle_tpu.executor import Scope, scope_guard

    dev = _require_chip()
    # bs128 measured best on v5e (r05 window 2: 1786 img/s vs 1599 at
    # bs64, 1747 at bs256 — deeper MXU pipelining per weight load)
    batch = 128
    bs_env = os.environ.get("PADDLE_BENCH_RESNET_BS")
    if bs_env:
        batch = int(bs_env)
    warmup, steps = 3, 60
    size = 224
    # NHWC A/B: channels-last is the TPU-native conv layout; whether
    # XLA's internal NCHW re-layout costs real transposes is empirical
    fmt = os.environ.get("PADDLE_BENCH_RESNET_FMT", "NCHW").upper()
    if fmt not in ("NCHW", "NHWC"):
        raise SystemExit("PADDLE_BENCH_RESNET_FMT must be NCHW or NHWC, "
                         "got %r" % fmt)
    # s2d A/B: the space-to-depth stem (models/resnet.py _s2d_stem)
    stem = os.environ.get("PADDLE_BENCH_RESNET_STEM", "conv7").lower()
    if stem not in ("conv7", "s2d"):
        raise SystemExit("PADDLE_BENCH_RESNET_STEM must be conv7 or "
                         "s2d, got %r" % stem)
    main_prog, startup, feeds, loss, acc = resnet.build(
        dataset="imagenet", amp=True, data_format=fmt, stem=stem)
    run_prog, steps, iters = _wrap_iters_per_run(main_prog, loss, steps)
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        rng = np.random.RandomState(0)
        img_shape = ((batch, 3, size, size) if fmt == "NCHW"
                     else (batch, size, size, 3))
        feed = {
            "img": jnp.asarray(rng.randn(*img_shape).astype("float32")),
            "label": jnp.asarray(
                rng.randint(0, 10, (batch, 1)).astype("int64")),
        }
        dt = _timed_steps(exe, run_prog, feed, loss, warmup, steps)
    ips = batch * steps * iters / dt
    mfu = ips * RESNET50_TRAIN_FLOPS_PER_IMAGE / peak_flops(dev)
    line = {
        "metric": "resnet50_imagenet_train_images_per_sec_per_chip",
        "value": round(ips, 1),
        "unit": "images/sec/chip (%dx%d bs%d bf16 AMP%s%s, MFU %.3f on %s)"
                % (size, size, batch,
                   " ipr%d" % iters if iters > 1 else "",
                   (" NHWC" if fmt == "NHWC" else "")
                   + (" s2d-stem" if stem == "s2d" else ""),
                   mfu, dev.device_kind),
        "vs_baseline": round(mfu / 0.45, 3),
    }
    print(json.dumps(line), flush=True)
    with scope_guard(scope):
        xla_flops = _xla_flops_per_step(scope, feed)
    if xla_flops:
        line.update(_mfu_fields(mfu, steps * iters / dt, xla_flops,
                                peak_flops(dev), band=(0.95, 1.9)))
        print(json.dumps(line), flush=True)


def child_infer():
    """ResNet-50 inference through the FULL reference-analogue stack:
    build eval graph → ``save_inference_model`` → ``AnalysisPredictor``
    (analysis pass pipeline: conv+bn fold, fc fuse, DCE) → timed
    pipelined batches.  Reference analogue: the inference comparison
    figures (``benchmark/figs/resnet-infer-*.png``) and
    ``paddle/fluid/inference/tests/api`` benchmarks; this is the
    inference-stack headline, not just a unit test."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.models.resnet import resnet_imagenet

    dev = _require_chip()
    batch = 256
    size = 224
    warmup, steps = 3, 60

    fmt = os.environ.get("PADDLE_BENCH_RESNET_FMT", "NCHW").upper()
    if fmt not in ("NCHW", "NHWC"):
        raise SystemExit("PADDLE_BENCH_RESNET_FMT must be NCHW or NHWC, "
                         "got %r" % fmt)
    img_shape = [3, size, size] if fmt == "NCHW" else [size, size, 3]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data("img", shape=img_shape, dtype="float32")
        logits = resnet_imagenet(img, 1000, 50, is_test=True,
                                 data_format=fmt)
        prob = fluid.layers.softmax(logits)
    # export stays fp32: the predictor folds conv+bn FIRST, then
    # bf16-rewrites via AnalysisConfig.enable_bf16 — rewriting before
    # export would cast-sandwich every bn and defeat the fold

    pred = _export_predictor(main, startup, ["img"], [prob],
                             "bench_infer_")
    rng = np.random.RandomState(0)
    feed = {"img": jnp.asarray(rng.randn(
        *((batch,) + tuple(img_shape))).astype("float32"))}

    lat_ms, dt, async_ms = _predictor_timing(pred, feed, warmup, steps)
    ips = batch * steps / dt
    _emit_sync_latency("resnet50_infer", async_ms, lat_ms, dev)
    # fwd-only model FLOPs: 2 x 4.09 GMACs at 224^2 (see the train
    # constant above)
    mfu = ips * (RESNET50_TRAIN_FLOPS_PER_IMAGE / 3) / peak_flops(dev)
    print(json.dumps({
        "metric": "resnet50_infer_images_per_sec_per_chip",
        "value": round(ips, 1),
        "unit": "images/sec/chip (%dx%d bs%d bf16%s AnalysisPredictor, "
                "sync latency %.1f ms/batch, MFU %.3f on %s)"
                % (size, size, batch,
                   " NHWC" if fmt == "NHWC" else "",
                   lat_ms, mfu, dev.device_kind),
        "vs_baseline": round(mfu / 0.45, 3),
    }), flush=True)


def child_bert_infer():
    """Own child mode (not chained onto child_infer): isolates failures
    and gives each inference benchmark its own time cap."""
    _bert_infer(_require_chip())


def _export_predictor(main, startup, feed_names, targets, prefix):
    """Shared export→predictor scaffold: save_inference_model into a
    tempdir, load through the analysis pipeline (+bf16 AFTER folding,
    via AnalysisConfig.enable_bf16), remove the tempdir."""
    import shutil
    import tempfile

    import paddle_tpu as fluid
    from paddle_tpu.executor import Scope, scope_guard

    export_dir = tempfile.mkdtemp(prefix=prefix)
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(export_dir, feed_names, targets,
                                      exe, main_program=main)
    print("# inference model exported", flush=True)
    cfg = fluid.inference.AnalysisConfig(model_dir=export_dir)
    cfg.enable_bf16()
    pred = fluid.inference.create_paddle_predictor(cfg)
    shutil.rmtree(export_dir, ignore_errors=True)
    print("# predictor built (analysis passes done)", flush=True)
    return pred


def _predictor_timing(pred, feed, warmup, steps, lat_runs=10):
    """Shared predictor measurement: sync per-request latency, pipelined
    serving throughput, and the ASYNC per-batch host-blocking latency
    (what one batch costs the serving loop when fetches stay lazy — the
    per-batch sync latency the fetch-handle path is meant to eliminate).
    Returns (lat_ms, dt_seconds, async_ms)."""
    def run_once(return_numpy=True):
        return pred.run(feed, return_numpy=return_numpy)

    # phase markers: when the time cap kills this child, the captured
    # stdout shows WHICH phase stalled
    t0 = time.perf_counter()
    for _ in range(warmup):
        run_once()
    print("# predictor warmup done in %.1fs" % (time.perf_counter() - t0),
          flush=True)
    # latency: synchronous single-batch round trips (what one request
    # pays, incl. the fetch)
    t0 = time.perf_counter()
    for _ in range(lat_runs):
        out = run_once()
    lat_ms = (time.perf_counter() - t0) / lat_runs * 1e3
    assert np.isfinite(out[0]).all()
    print("# predictor sync latency %.1f ms/batch" % lat_ms, flush=True)
    # throughput: pipelined batches (serving style — overlap dispatch),
    # synced by a data FETCH of the last output: execution is in-order,
    # so the final fetch closes the whole pipeline
    t0 = time.perf_counter()
    outs = [run_once(return_numpy=False) for _ in range(steps)]
    np.asarray(outs[-1][0])
    dt = time.perf_counter() - t0
    # async per-batch host-blocking latency: each run_async-style call
    # returns lazy fetch handles the moment the step is enqueued — the
    # per-call wall time is ALL a pipelined serving loop pays per batch
    # (vs lat_ms for the blocking round trip); one final fetch closes
    # the window so in-flight work is not billed to the next phase
    blocked = 0.0
    tail = None
    for _ in range(lat_runs):
        t1 = time.perf_counter()
        tail = pred.run_async(feed)
        blocked += time.perf_counter() - t1
    np.asarray(tail[0])
    async_ms = blocked / lat_runs * 1e3
    print("# predictor async dispatch latency %.2f ms/batch" % async_ms,
          flush=True)
    return lat_ms, dt, async_ms


def _emit_sync_latency(base_metric, async_ms, lat_ms, dev):
    """BENCH line: per-batch sync latency of the async serving loop
    (single-digit ms is the target; the blocking round trip rides in
    the unit for contrast).  vs_baseline >= 1 once the per-batch
    host-blocking time is under the 10 ms bar."""
    print(json.dumps({
        "metric": base_metric + "_sync_latency_ms",
        "value": round(async_ms, 2),
        "unit": "ms/batch host-blocking (async fetch-handle loop; "
                "blocking round-trip %.1f ms/batch on %s)"
                % (lat_ms, getattr(dev, "device_kind", str(dev))),
        "vs_baseline": round(10.0 / max(async_ms, 1e-3), 3),
    }), flush=True)


def _bert_infer(dev, seq_len=128):
    """BERT encoder serving (bert-as-a-service feature extraction)
    through the same export → AnalysisPredictor path — the NLP half of
    the inference headline (reference analogue: the ernie/bert models
    under ``paddle/fluid/inference/tests/api``)."""
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.models import bert

    cfg = bert.BERT_BASE
    batch = 32
    warmup, steps = 3, 40

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        input_ids = fluid.layers.data("input_ids", shape=[seq_len],
                                      dtype="int64")
        token_type = fluid.layers.data("token_type_ids", shape=[seq_len],
                                       dtype="int64")
        mask = fluid.layers.data("attn_mask_bias",
                                 shape=[1, 1, seq_len], dtype="float32")
        import copy

        icfg = copy.copy(cfg)
        icfg.dropout = 0.0
        icfg.attn_dropout = 0.0
        hidden = bert.encoder(input_ids, token_type, mask, icfg, seq_len)

    pred = _export_predictor(
        main, startup,
        ["input_ids", "token_type_ids", "attn_mask_bias", "pos_ids"],
        [hidden], "bench_bert_infer_")

    rng = np.random.RandomState(0)
    # feed layout comes from the single source of truth
    # (bert.make_fake_batch "must agree" with the model); the encoder
    # export needs only the 4 input feeds, not the MLM labels
    feed_names = ("input_ids", "token_type_ids", "attn_mask_bias",
                  "pos_ids")
    feed = {k: jnp.asarray(v)
            for k, v in bert.make_fake_batch(batch, seq_len, cfg, rng,
                                             max_pred=0).items()
            if k in feed_names}
    lat_ms, dt, async_ms = _predictor_timing(pred, feed, warmup, steps)
    tps = batch * seq_len * steps / dt
    _emit_sync_latency("bert_base_infer", async_ms, lat_ms, dev)
    d, ff = cfg.hidden, cfg.ffn
    fwd_flops_per_token = cfg.layers * (
        8 * d * d + 4 * d * ff + 4 * seq_len * d)
    mfu = tps * fwd_flops_per_token / peak_flops(dev)
    print(json.dumps({
        "metric": "bert_base_infer_tokens_per_sec_per_chip",
        "value": round(tps, 1),
        "unit": "tokens/sec/chip (encoder fwd seq%d bs%d bf16 "
                "AnalysisPredictor, sync latency %.1f ms/batch, "
                "MFU %.3f on %s)"
                % (seq_len, batch, lat_ms, mfu, dev.device_kind),
        "vs_baseline": round(mfu / 0.45, 3),
    }), flush=True)


def child_fusion():
    """Fusion pass pipeline A/B (ISSUE 5): the same mnist-shaped MLP
    train step with PADDLE_TPU_FUSION on vs off, plus the fused-op
    census of the bert-tiny train program (IR-only).  Emits
    ``*_fusion_speedup`` (>1 = fusion wins) and fused-op counts so the
    pipeline's effect is visible next to every other BENCH line."""
    import paddle_tpu as fluid
    from paddle_tpu.executor import Scope, scope_guard
    from paddle_tpu.static_analysis import fusion

    def build():
        fluid.unique_name.switch()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            img = fluid.layers.data(name="img", shape=[784],
                                    dtype="float32")
            label = fluid.layers.data(name="label", shape=[1],
                                      dtype="int64")
            h = fluid.layers.fc(input=img, size=200, act="relu")
            h = fluid.layers.fc(input=h, size=200, act="relu")
            pred = fluid.layers.fc(input=h, size=10, act="softmax")
            loss = fluid.layers.reduce_mean(
                fluid.layers.cross_entropy(input=pred, label=label))
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
        return main, startup, loss

    rng = np.random.RandomState(0)
    feed = {"img": rng.rand(64, 784).astype("float32"),
            "label": rng.randint(0, 10, (64, 1)).astype("int64")}
    warmup, steps = 3, 30
    times = {}
    for arm in ("1", "0"):
        os.environ["PADDLE_TPU_FUSION"] = arm
        main, startup, loss = build()
        exe = fluid.Executor()
        with scope_guard(Scope()):
            exe.run(startup)
            times[arm] = _timed_steps(exe, main, feed, loss.name,
                                      warmup, steps)
    os.environ.pop("PADDLE_TPU_FUSION", None)
    speedup = times["0"] / times["1"] if times["1"] else 0.0
    main, startup, loss = build()
    _, report = fusion.resolve_fused_program(main, targets=[loss.name])
    dev = jax_backend_name()
    print(json.dumps({
        "metric": "mnist_mlp_train_fusion_speedup",
        "value": round(speedup, 4),
        "unit": "x (fusion-off step time / fusion-on, %d steps, %s)"
                % (steps, dev),
        "fused_op_counts": report.counts(),
        "ops_removed": report.ops_removed,
    }), flush=True)

    # bert-tiny train program census (IR-only, no execution): how many
    # subgraphs each family rewrites at the default config
    import copy as _copy

    from paddle_tpu.models import bert

    cfg = _copy.copy(bert.BERT_TINY)
    cfg.fuse_attn = False
    fluid.unique_name.switch()
    bmain, _, _, bloss = bert.build_pretrain(cfg, seq_len=32, train=True)
    n_before = len(bmain.global_block().ops)
    bfused, brep = fusion.resolve_fused_program(
        bmain, targets=[bloss.name])
    print(json.dumps({
        "metric": "bert_tiny_train_fused_op_count",
        "value": sum(brep.counts().values()),
        "unit": "rewrites (program ops %d -> %d)"
                % (n_before, len(bfused.global_block().ops)),
        "fused_op_counts": brep.counts(),
    }), flush=True)


def child_observability():
    """Telemetry overhead A/B (ISSUE 9): the same mnist-shaped MLP
    train loop with the metrics/journal/drift layer fully ON (journal
    dir set, so real JSONL writes happen) vs killed via the
    ``PADDLE_TPU_TELEMETRY`` switch.  Emits ``telemetry_overhead_pct``
    — the acceptance gate is < 2%.  Min-over-repeats on both arms so a
    scheduler hiccup on either side doesn't fake (or hide) overhead."""
    import shutil
    import tempfile

    import paddle_tpu as fluid
    from paddle_tpu.executor import Scope, scope_guard
    from paddle_tpu.observability import (metrics as _om,
                                          reset_telemetry)

    def build():
        fluid.unique_name.switch()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            img = fluid.layers.data(name="img", shape=[784],
                                    dtype="float32")
            label = fluid.layers.data(name="label", shape=[1],
                                      dtype="int64")
            h = fluid.layers.fc(input=img, size=200, act="relu")
            h = fluid.layers.fc(input=h, size=200, act="relu")
            pred = fluid.layers.fc(input=h, size=10, act="softmax")
            loss = fluid.layers.reduce_mean(
                fluid.layers.cross_entropy(input=pred, label=label))
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
        return main, startup, loss

    rng = np.random.RandomState(0)
    feed = {"img": rng.rand(64, 784).astype("float32"),
            "label": rng.randint(0, 10, (64, 1)).astype("int64")}
    warmup, steps, repeats = 10, 100, 5
    tdir = tempfile.mkdtemp(prefix="paddle_tpu_obs_bench_")
    times = {"on": None, "off": None}
    # the drift->autotune calibration write is a one-shot per run that
    # forces a jit recompile (state_token churn) — steady-state per-step
    # overhead is what the <2% gate means, so pin recording off here
    os.environ["PADDLE_TPU_DRIFT_RECORD"] = "0"
    os.environ["PADDLE_TPU_TELEMETRY_DIR"] = tdir
    reset_telemetry()
    try:
        # ONE build/compile, telemetry registered; the arms then toggle
        # the kill switch over interleaved windows of the same jitted
        # step — a separate process/executor per arm would hand the
        # metric to CPU-frequency and compile-state noise an order of
        # magnitude larger than the effect being measured
        _om.set_telemetry_enabled(True)
        main, startup, loss = build()
        exe = fluid.Executor()
        with scope_guard(Scope()):
            exe.run(startup)
            lv = exe.run(main, feed=feed, fetch_list=[loss.name])[0]
            assert np.isfinite(lv).all()
            for _ in range(warmup):
                exe.run(main, feed=feed, fetch_list=[])
            for _ in range(repeats):
                for arm in ("on", "off"):
                    _om.set_telemetry_enabled(arm == "on")
                    t0 = time.perf_counter()
                    for _ in range(steps):
                        exe.run(main, feed=feed, fetch_list=[])
                    t = time.perf_counter() - t0
                    if times[arm] is None or t < times[arm]:
                        times[arm] = t
    finally:
        _om.set_telemetry_enabled(None)
        reset_telemetry()
        os.environ.pop("PADDLE_TPU_TELEMETRY_DIR", None)
        os.environ.pop("PADDLE_TPU_DRIFT_RECORD", None)
        shutil.rmtree(tdir, ignore_errors=True)
    overhead = ((times["on"] - times["off"]) / times["off"] * 100.0
                if times["off"] else 0.0)
    dev = jax_backend_name()
    print(json.dumps({
        "metric": "telemetry_overhead_pct",
        "value": round(overhead, 3),
        "unit": "%% step-time delta, telemetry on vs off (%d steps x%d "
                "min, %s; gate < 2)" % (steps, repeats, dev),
        "on_s": round(times["on"], 4),
        "off_s": round(times["off"], 4),
    }), flush=True)


def child_tracing():
    """Tracing overhead A/B (ISSUE 13): the same mnist-shaped MLP train
    loop with distributed tracing ON (executor.step/dispatch spans,
    JSONL flushes into a real dir) vs killed via ``PADDLE_TPU_TRACING``
    — telemetry itself stays ON in both arms so the delta isolates the
    span layer.  Emits ``tracing_overhead_pct``; the acceptance gate is
    < 2%.  Min-over-repeats on both arms, same discipline as
    ``child_observability``."""
    import shutil
    import tempfile

    import paddle_tpu as fluid
    from paddle_tpu.executor import Scope, scope_guard
    from paddle_tpu.observability import (metrics as _om,
                                          tracing as _otr,
                                          reset_telemetry)

    def build():
        fluid.unique_name.switch()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            img = fluid.layers.data(name="img", shape=[784],
                                    dtype="float32")
            label = fluid.layers.data(name="label", shape=[1],
                                      dtype="int64")
            h = fluid.layers.fc(input=img, size=200, act="relu")
            h = fluid.layers.fc(input=h, size=200, act="relu")
            pred = fluid.layers.fc(input=h, size=10, act="softmax")
            loss = fluid.layers.reduce_mean(
                fluid.layers.cross_entropy(input=pred, label=label))
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
        return main, startup, loss

    rng = np.random.RandomState(0)
    feed = {"img": rng.rand(64, 784).astype("float32"),
            "label": rng.randint(0, 10, (64, 1)).astype("int64")}
    warmup, steps, repeats = 10, 200, 7
    tdir = tempfile.mkdtemp(prefix="paddle_tpu_trace_bench_")
    times = {"on": None, "off": None}
    os.environ["PADDLE_TPU_DRIFT_RECORD"] = "0"
    os.environ["PADDLE_TPU_TELEMETRY_DIR"] = tdir
    reset_telemetry()
    try:
        # ONE build/compile; the arms toggle only the tracing kill
        # switch over interleaved windows of the same jitted step
        _om.set_telemetry_enabled(True)
        main, startup, loss = build()
        exe = fluid.Executor()
        with scope_guard(Scope()):
            exe.run(startup)
            lv = exe.run(main, feed=feed, fetch_list=[loss.name])[0]
            assert np.isfinite(lv).all()
            for _ in range(warmup):
                exe.run(main, feed=feed, fetch_list=[])
            for rep in range(repeats):
                # alternate which arm goes first so frequency drift /
                # cache-warming bias doesn't systematically charge one
                order = ("on", "off") if rep % 2 == 0 else ("off", "on")
                for arm in order:
                    _otr.set_tracing_enabled(arm == "on")
                    t0 = time.perf_counter()
                    for _ in range(steps):
                        exe.run(main, feed=feed, fetch_list=[])
                    t = time.perf_counter() - t0
                    if times[arm] is None or t < times[arm]:
                        times[arm] = t
    finally:
        _otr.set_tracing_enabled(None)
        _om.set_telemetry_enabled(None)
        reset_telemetry()
        os.environ.pop("PADDLE_TPU_TELEMETRY_DIR", None)
        os.environ.pop("PADDLE_TPU_DRIFT_RECORD", None)
        shutil.rmtree(tdir, ignore_errors=True)
    overhead = ((times["on"] - times["off"]) / times["off"] * 100.0
                if times["off"] else 0.0)
    dev = jax_backend_name()
    print(json.dumps({
        "metric": "tracing_overhead_pct",
        "value": round(overhead, 3),
        "unit": "%% step-time delta, tracing on vs off (%d steps x%d "
                "min, %s; gate < 2)" % (steps, repeats, dev),
        "on_s": round(times["on"], 4),
        "off_s": round(times["off"], 4),
    }), flush=True)


def child_kernels():
    """Kernel-gap A/Bs (ISSUE 6): (1) the conv+BN+act fusion family on
    the ResNet trainer — same program with the family cost-gated off vs
    on (single-variable A/B via PADDLE_TPU_CONV_BN_MIN_BYTES; everything
    else identical) — and (2) DeepFM with HOST-resident embedding tables
    vs device-resident tables (the Pallas gather path).  Emits
    ``resnet50_conv_fusion_speedup`` and ``deepfm_device_table_speedup``
    with fused-op counts so the kernel work is visible next to every
    other BENCH line."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.executor import Scope, scope_guard
    from paddle_tpu.models import resnet, ctr
    from paddle_tpu.static_analysis import fusion

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    kind = getattr(dev, "device_kind", str(dev))

    # ---- conv+BN+act fusion A/B ----
    batch = 128 if on_tpu else 4
    size = 224 if on_tpu else 32
    warmup, steps = (3, 30) if on_tpu else (1, 3)

    def build_resnet():
        fluid.unique_name.switch()
        return resnet.build(
            dataset="imagenet" if on_tpu else "cifar10", amp=on_tpu)

    rng = np.random.RandomState(0)
    feed = {
        "img": jnp.asarray(rng.randn(batch, 3, size, size)
                           .astype("float32")),
        "label": jnp.asarray(rng.randint(0, 10, (batch, 1))
                             .astype("int64")),
    }
    times = {}
    for arm, gate in (("off", "1000000000000"), ("on", "")):
        if gate:
            os.environ["PADDLE_TPU_CONV_BN_MIN_BYTES"] = gate
        else:
            os.environ.pop("PADDLE_TPU_CONV_BN_MIN_BYTES", None)
        main_prog, startup, feeds, loss, acc = build_resnet()
        exe = fluid.Executor(fluid.TPUPlace())
        with scope_guard(Scope()):
            exe.run(startup)
            times[arm] = _timed_steps(exe, main_prog, feed, loss, warmup,
                                      steps)
    os.environ.pop("PADDLE_TPU_CONV_BN_MIN_BYTES", None)
    main_prog, startup, feeds, loss, acc = build_resnet()
    _, report = fusion.resolve_fused_program(main_prog,
                                             targets=[loss.name])
    speedup = times["off"] / times["on"] if times["on"] else 0.0
    print(json.dumps({
        "metric": "resnet50_conv_fusion_speedup",
        "value": round(speedup, 4),
        "unit": "x (conv_bn_act family off / on, %s resnet %dx%d bs%d, "
                "%d steps on %s)"
                % ("imagenet-50" if on_tpu else "cifar-smoke", size,
                   size, batch, steps, kind),
        "fused_op_counts": report.counts(),
        "conv_bn_act_sites": report.counts().get("conv_bn_act", 0),
        "vs_baseline": round(speedup, 3),
    }), flush=True)

    # ---- DeepFM host-table vs device-table A/B ----
    # dim 128 so the device arm's gather is lane-aligned (the Pallas
    # row-DMA eligibility) — the host arm uses the same dim for a fair
    # bytes-moved comparison.  vocab 200k (not the ctr child's 1M): the
    # device arm must FIT — 8 tables of 1M x 128 f32 would be 4.1 GB of
    # params + 8.2 GB Adam moments + ~4 GB of live dense scatter-add
    # grads, over a 16 GB-HBM chip; at 200k the whole arm is ~3.3 GB
    batch = 4096 if on_tpu else 256
    vocab = 200_000 if on_tpu else 20_000
    num_slots, slot_len, dim = 8, 4, 128
    warmup, steps = (2, 30) if on_tpu else (1, 4)
    feed = {"slot_%d" % i: rng.randint(
        0, vocab, (batch, slot_len)).astype("int64")
        for i in range(num_slots)}
    feed["label"] = rng.randint(0, 2, (batch, 1)).astype("int64")
    times = {}
    for arm in ("host", "device"):
        from paddle_tpu import host_table

        host_table.reset_tables()
        fluid.unique_name.switch()
        main_prog, startup, feeds, loss, prob = ctr.build(
            model="deepfm", num_slots=num_slots, slot_len=slot_len,
            vocab=vocab, embed_dim=dim,
            use_host_table=(arm == "host"))
        exe = fluid.Executor(fluid.TPUPlace())
        with scope_guard(Scope()):
            exe.run(startup)
            times[arm] = _timed_steps(exe, main_prog, feed, loss,
                                      warmup, steps)
    speedup = times["host"] / times["device"] if times["device"] else 0.0
    fluid.unique_name.switch()
    main_prog, startup, feeds, loss, prob = ctr.build(
        model="deepfm", num_slots=num_slots, slot_len=slot_len,
        vocab=vocab, embed_dim=dim, use_host_table=False)
    _, report = fusion.resolve_fused_program(main_prog,
                                             targets=[loss.name])
    print(json.dumps({
        "metric": "deepfm_device_table_speedup",
        "value": round(speedup, 4),
        "unit": "x (host-resident tables / device-resident, V=%d D=%d "
                "bs%d, %d steps on %s)"
                % (vocab, dim, batch, steps, kind),
        "fused_op_counts": report.counts(),
        "embedding_gather_sites": report.counts().get(
            "embedding_gather", 0),
        "vs_baseline": round(speedup, 3),
    }), flush=True)


def child_serving():
    """Continuous-batching serving benchmark (ISSUE 11): two
    co-resident tenants — the mnist-shaped MLP and the bert encoder —
    behind one ``paddle_tpu.serving.PredictorServer``.  The placement
    passes the scope-overlap proof and every tenant's hot loop passes
    the zero-sync certificate under ``PADDLE_TPU_STRICT_SYNC=1`` (both
    enforced at server construction).  Runs a fixed-QPS load (latency
    percentiles, shed-rate gate) plus a saturation A/B of continuous
    batching vs naive one-request-per-step dispatch at the same
    request mix.  Hard gates (exit 1): certificate pass, shed == 0 and
    rejected == 0 at the smoke QPS, and jit-cache entries bounded by
    the bucket count (no unbounded compile growth)."""
    import copy

    import jax

    import paddle_tpu as fluid
    from paddle_tpu import serving
    from paddle_tpu.models import bert

    os.environ["PADDLE_TPU_STRICT_SYNC"] = "1"
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    seq_len = 64 if on_tpu else 32

    # tenant 1: the mnist MLP (examples/mnist_train.py shape), eval form
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data("img", shape=[784], dtype="float32")
        h = fluid.layers.fc(img, size=200, act="relu")
        h = fluid.layers.fc(h, size=200, act="relu")
        prob = fluid.layers.softmax(fluid.layers.fc(h, size=10))
    mnist_pred = _export_predictor(main, startup, ["img"], [prob],
                                   on_tpu, "bench_serve_mnist_")

    # tenant 2: the bert encoder (feature-extraction serving)
    cfg = bert.BERT_BASE if on_tpu else bert.BERT_TINY
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        input_ids = fluid.layers.data("input_ids", shape=[seq_len],
                                      dtype="int64")
        token_type = fluid.layers.data("token_type_ids",
                                       shape=[seq_len], dtype="int64")
        mask = fluid.layers.data("attn_mask_bias",
                                 shape=[1, 1, seq_len], dtype="float32")
        icfg = copy.copy(cfg)
        icfg.dropout = 0.0
        icfg.attn_dropout = 0.0
        hidden = bert.encoder(input_ids, token_type, mask, icfg,
                              seq_len)
    bert_feeds = ("input_ids", "token_type_ids", "attn_mask_bias",
                  "pos_ids")
    bert_pred = _export_predictor(main, startup, list(bert_feeds),
                                  [hidden], on_tpu,
                                  "bench_serve_bert_")

    rng = np.random.RandomState(0)

    def mnist_sample():
        return {"img": rng.randn(1, 784).astype("float32")}

    def bert_sample():
        return {k: v for k, v in bert.make_fake_batch(
            1, seq_len, cfg, rng, max_pred=0).items()
            if k in bert_feeds}

    samplers = {"mnist": mnist_sample, "bert": bert_sample}
    buckets = (1, 2, 4, 8)
    preds = {"mnist": mnist_pred, "bert": bert_pred}

    def make_server(bucket_set, max_in_flight, queue_cap=1024):
        # construction runs the scope-overlap proof + per-tenant
        # zero-sync verification; a VerifyError here IS the gate firing
        return serving.PredictorServer(
            preds, max_in_flight=max_in_flight, buckets=bucket_set,
            queue_cap=queue_cap, auto_start=False)

    server = make_server(buckets, max_in_flight=3)
    assert all(c.ok for c in server.certificates.values()), \
        "zero-sync certificate failed: %s" % server.certificates
    print("# serving gates: scope-overlap proof + zero-sync "
          "certificates PASS (%s)" % list(server.certificates),
          flush=True)
    server.warmup({t: samplers[t]() for t in preds})
    print("# serving warmup done (%d bucket signatures per tenant)"
          % len(buckets), flush=True)
    # arm 1: fixed-QPS smoke — latency percentiles under a generous SLA
    qps = 120.0 if on_tpu else 60.0
    n_req = 360 if on_tpu else 120
    server.start()
    fixed = serving.run_load(server, samplers, qps=qps,
                             requests=n_req, sla_ms=5000.0)
    server.close()
    print("# fixed-qps arm: %s" % json.dumps(
        {k: fixed[k] for k in ("completed", "shed", "rejected",
                               "p50_ms", "p99_ms", "qps")}),
        flush=True)

    # arm 2 A/B at saturation: naive one-request-per-step dispatch
    # (bucket {1}, in-flight window 1) vs continuous batching, same mix
    naive = make_server((1,), max_in_flight=1)
    naive.warmup({t: samplers[t]() for t in preds})
    rep_naive = serving.run_load(naive.start(), samplers,
                                 requests=n_req, burst=True)
    naive.close()
    cont = make_server(buckets, max_in_flight=3)
    cont.warmup({t: samplers[t]() for t in preds})
    rep_cont = serving.run_load(cont.start(), samplers,
                                requests=n_req, burst=True)
    cont.close()
    speedup = rep_cont["qps"] / max(rep_naive["qps"], 1e-9)
    print("# saturation A/B: continuous %.1f qps (p99 %.1fms) vs "
          "naive %.1f qps (p99 %.1fms)"
          % (rep_cont["qps"], rep_cont["p99_ms"] or 0,
             rep_naive["qps"], rep_naive["p99_ms"] or 0), flush=True)

    # hard gates
    errors = []
    if fixed["shed"] or fixed["rejected"] or fixed["failed"]:
        errors.append("fixed-qps arm shed/rejected/failed: %d/%d/%d"
                      % (fixed["shed"], fixed["rejected"],
                         fixed["failed"]))
    for name, pred in preds.items():
        entries = len(pred._exe._cache)
        if entries > len(buckets):
            errors.append(
                "tenant %s jit cache grew past the bucket cap: "
                "%d entries > %d buckets" % (name, entries,
                                             len(buckets)))

    kind = getattr(dev, "device_kind", str(dev))
    print(json.dumps({
        "metric": "p50_serving_latency_ms",
        "value": round(fixed["p50_ms"], 2),
        "unit": "ms (2 tenants mnist+bert seq%d, %.0f qps offered, "
                "buckets %s, in-flight 3, on %s)"
                % (seq_len, qps, list(buckets), kind),
        "vs_baseline": round(100.0 / max(fixed["p50_ms"], 1e-3), 3),
    }), flush=True)
    print(json.dumps({
        "metric": "p99_serving_latency_ms",
        "value": round(fixed["p99_ms"], 2),
        "unit": "ms (2 tenants, %.0f qps offered, shed=%d rejected=%d, "
                "zero-sync certified, on %s)"
                % (qps, fixed["shed"], fixed["rejected"], kind),
        "vs_baseline": round(250.0 / max(fixed["p99_ms"], 1e-3), 3),
    }), flush=True)
    print(json.dumps({
        "metric": "serving_throughput_qps",
        "value": round(rep_cont["qps"], 1),
        "unit": "req/sec at saturation (continuous batching p99 "
                "%.1fms vs naive 1-req/step %.1f qps p99 %.1fms)"
                % (rep_cont["p99_ms"] or 0, rep_naive["qps"],
                   rep_naive["p99_ms"] or 0),
        "vs_baseline": round(speedup, 3),
    }), flush=True)
    print(json.dumps({
        "metric": "serving_continuous_batching_speedup",
        "value": round(speedup, 3),
        "unit": "x naive dispatch throughput (%d reqs, 2 tenants)"
                % n_req,
        "vs_baseline": round(speedup, 3),
    }), flush=True)

    if errors:
        for e in errors:
            print("# SERVING GATE FAILED: %s" % e, file=sys.stderr,
                  flush=True)
        raise SystemExit(1)


def child_decode():
    """Autoregressive decoding benchmark (ISSUE 14): the
    examples/gpt_small KV-cache generation loop (device-resident ring
    cache + flash-decode attention + while-op decode_loop — ONE jit
    entry for the whole generation) A/B'd against the naive
    full-recompute baseline (re-run the full forward over the Tmax
    token buffer every step) at the same (batch, prompt, max_new) and
    the same Tmax=512 capacity.  Emits
    ``gpt_small_decode_tokens_per_sec`` and
    ``gpt_small_time_to_first_token_ms``; the measured A/B is recorded
    into the autotune ``decode`` family, and on TPU a kernel micro-sweep
    writes the ``decode_min_t`` engagement threshold (the CPU smoke
    records the conservative default under backend=cpu).  A second
    section (ISSUE 19) drives the paged serving tier: paged-pool vs
    slot-ring stream capacity at equal HBM, bit-identical greedy +
    ``PADDLE_TPU_PAGED_KV=0`` kill-switch restore, disaggregated
    prefill/decode under the scope proof + zero-sync certificate, and
    ngram speculative decoding.  Hard gates (exit 1): KV-cache path
    >= 2x the naive tokens/sec; paged streams >= 4x ring slots at
    equal HBM with identical tokens; speculation emits identical
    tokens at >= the non-speculative tokens/sec."""
    import jax

    from paddle_tpu import autotune

    repo = os.path.dirname(os.path.abspath(__file__))
    ex = os.path.join(repo, "examples")
    if ex not in sys.path:
        sys.path.insert(0, ex)
    import gpt_small

    os.environ["PADDLE_TPU_STRICT_SYNC"] = "1"
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    kind = getattr(dev, "device_kind", str(dev))

    cfg = gpt_small.GPT_TINY  # Tmax=512: the naive arm pays full
    batch = 8 if on_tpu else 2          # recompute over all 512 slots
    prompt = 32 if on_tpu else 8
    new = 64 if on_tpu else 32

    def kv_build():
        return gpt_small.build_program(cfg, batch, prompt, new)

    def naive_build():
        return gpt_small.build_naive_program(cfg, batch, prompt, new)

    toks_kv, _glen, ttft_kv, tps_kv = gpt_small.run_generate(
        kv_build, cfg, batch, prompt, new)
    toks_nv, _glen, ttft_nv, tps_nv = gpt_small.run_generate(
        naive_build, cfg, batch, prompt, new)
    if toks_kv.tolist() != toks_nv.tolist():
        print("# DECODE GATE FAILED: kv-cache and naive paths disagree "
              "on greedy tokens", file=sys.stderr, flush=True)
        raise SystemExit(1)
    speedup = tps_kv / max(tps_nv, 1e-9)

    sig = autotune.sweep_signature(
        "decode", {"model": "gpt_small", "tmax": cfg.max_len,
                   "batch": batch, "prompt": prompt, "new": new})
    autotune.record(sig, {
        "tokens_per_sec": round(tps_kv, 2),
        "naive_tokens_per_sec": round(tps_nv, 2),
        "ttft_ms": round(ttft_kv * 1e3, 2),
        "speedup": round(speedup, 3),
    })

    if on_tpu:
        # kernel engagement sweep: flash-decode vs the XLA composite
        # per cache length; the crossover is the recorded min_t
        import jax.numpy as jnp

        from paddle_tpu.ops.pallas import flash_decode as fd

        rng = np.random.RandomState(0)
        bh, d = 8, cfg.hidden // cfg.heads
        rows, min_t = {}, None

        def timed(fn, *a):
            jax.block_until_ready(fn(*a))  # compile outside the timing
            t0 = time.perf_counter()
            for _ in range(10):
                r = fn(*a)
            jax.block_until_ready(r)
            return (time.perf_counter() - t0) / 10

        kernel_fn = jax.jit(lambda q, k, v, l: fd.flash_decode(q, k, v, l))
        ref_fn = jax.jit(lambda q, k, v, l: fd.decode_reference(q, k, v, l))
        for t in (256, 512, 1024, 2048):
            q = jnp.asarray(rng.randn(bh, cfg.heads, d), jnp.float32)
            k = jnp.asarray(rng.randn(bh, cfg.heads, t, d), jnp.float32)
            v = jnp.asarray(rng.randn(bh, cfg.heads, t, d), jnp.float32)
            lens = jnp.full((bh,), t, jnp.int32)
            os.environ["PADDLE_TPU_DECODE_MIN_T"] = "1"  # force kernel
            try:
                ker = timed(kernel_fn, q, k, v, lens)
            finally:
                os.environ.pop("PADDLE_TPU_DECODE_MIN_T", None)
            ref = timed(ref_fn, q, k, v, lens)
            rows[t] = (ker, ref)
            if min_t is None and ker < ref:
                min_t = t
        autotune.record_decode_min_t(min_t or fd.DEFAULT_MIN_T,
                                     rows=rows)
        print("# decode_min_t sweep: %s -> min_t=%s"
              % ({t: (round(c * 1e6), round(b * 1e6))
                  for t, (c, b) in rows.items()},
                 min_t or fd.DEFAULT_MIN_T), flush=True)

    label = ("gpt_small" if not on_tpu else "gpt_small_tpu")
    print(json.dumps({
        "metric": "gpt_small_decode_tokens_per_sec",
        "value": round(tps_kv, 1),
        "unit": "tokens/sec (%s bs%d prompt%d new%d Tmax%d, KV-cache "
                "decode_loop vs naive full-recompute %.1f tok/s -> "
                "%.1fx, on %s)"
                % (label, batch, prompt, new, cfg.max_len, tps_nv,
                   speedup, kind),
        "vs_baseline": round(speedup / 2.0, 3),  # bar: >= 2x naive
    }), flush=True)
    print(json.dumps({
        "metric": "gpt_small_time_to_first_token_ms",
        "value": round(ttft_kv * 1e3, 1),
        "unit": "ms (first run incl jit compile; naive arm %.1f ms; "
                "steady decode is the tokens_per_sec line)"
                % (ttft_nv * 1e3),
        "vs_baseline": round(ttft_nv / max(ttft_kv, 1e-9), 3),
    }), flush=True)

    if speedup < 2.0:
        print("# DECODE GATE FAILED: kv-cache %.1f tok/s < 2x naive "
              "%.1f tok/s" % (tps_kv, tps_nv), file=sys.stderr,
              flush=True)
        raise SystemExit(1)

    # ---- ISSUE 19: paged KV pool + disaggregation + speculation ----
    import paddle_tpu as fluid
    from paddle_tpu import serving
    from paddle_tpu.ops.pallas import flash_decode as fd
    from paddle_tpu.ops.pallas.paged_flash_decode import paged_block_len
    from paddle_tpu.serving import blocks_needed

    errors = []
    max_len = 256 if on_tpu else 128
    new2 = 32 if on_tpu else 16
    bucket = 8
    ring_slots = 2
    n_stream = 8
    dh = cfg.hidden // cfg.heads
    bl = paged_block_len(dh, max_len)
    # equal HBM by construction: the paged pool holds exactly the rows
    # the 2-slot ring holds, carved into blocks
    pool_blocks = ring_slots * max_len // bl
    per_req = blocks_needed(bucket + new2, bl)
    paged_streams = min(n_stream, pool_blocks // per_req)
    rng2 = np.random.RandomState(7)
    prompts = [rng2.randint(1, cfg.vocab - 1,
                            size=rng2.randint(3, bucket)).tolist()
               for _ in range(n_stream)]
    gen_cfg = dict(prompt_buckets=(bucket,),
                   config=serving.GenerationConfig(max_new_tokens=new2))

    def adapter():
        return gpt_small.DecodeAdapter(cfg, max_len=max_len, seed=7)

    def run_streams(eng):
        """Submit every prompt; drain while sampling the concurrency
        high-water mark; return (tokens, latencies_ms, high_water)."""
        futs = [eng.submit(p) for p in prompts]
        hw, deadline = 0, time.time() + 600
        while time.time() < deadline:
            st = eng.stats()
            hw = max(hw, st["active_slots"])
            if not (st["active_slots"] or st["queue_depth"]
                    or st["handoff_depth"]):
                break
            time.sleep(0.001)
        toks = [f.result(timeout=120)[0] for f in futs]
        lats = [f.latency_ms for f in futs]
        return toks, lats, hw

    def p99(lats):
        return serving.percentile(sorted(lats), 99.0) or 0.0

    fluid.unique_name.switch()
    ring_eng = serving.DecodeEngine(adapter(), slots=ring_slots,
                                    paged=False, name="ring", **gen_cfg)
    try:
        ring_toks, ring_lats, _hw = run_streams(ring_eng)
        ring_bytes = ring_eng.cache_bytes
    finally:
        ring_eng.close()

    fluid.unique_name.switch()
    paged_eng = serving.DecodeEngine(adapter(), slots=paged_streams,
                                     paged=True,
                                     num_blocks=pool_blocks,
                                     name="paged", **gen_cfg)
    try:
        paged_toks, paged_lats, hw = run_streams(paged_eng)
        paged_bytes = paged_eng.cache_bytes
    finally:
        paged_eng.close()

    if paged_bytes != ring_bytes:
        errors.append("paged pool is not HBM-equal to the ring: "
                      "%d vs %d bytes" % (paged_bytes, ring_bytes))
    if paged_toks != ring_toks:
        errors.append("paged greedy diverged from the slot-ring greedy")
    stream_ratio = paged_streams / float(ring_slots)
    if stream_ratio < 4.0:
        errors.append("paged streams %d < 4x ring slots %d at equal "
                      "HBM" % (paged_streams, ring_slots))
    if hw < paged_streams:
        errors.append("paged concurrency high-water %d never reached "
                      "the pool capacity %d" % (hw, paged_streams))

    # kill switch: PADDLE_TPU_PAGED_KV=0 must put the SAME paged-capable
    # model back on the ring path, bit-exactly
    os.environ[serving.PAGED_KV_ENV] = "0"
    try:
        fluid.unique_name.switch()
        kill_eng = serving.DecodeEngine(adapter(), slots=ring_slots,
                                        name="killsw", **gen_cfg)
        try:
            if kill_eng.paged:
                errors.append("kill switch did not disable paging")
            kill_toks, _l, _h = run_streams(kill_eng)
        finally:
            kill_eng.close()
    finally:
        os.environ.pop(serving.PAGED_KV_ENV, None)
    if kill_toks != ring_toks:
        errors.append("kill-switch engine diverged from the ring path")

    # disaggregated tenants: prefill + decode co-resident under the
    # scope-overlap proof and the zero-sync certificate (STRICT_SYNC=1
    # is already set above); handoff must not change tokens
    fluid.unique_name.switch()
    dis_eng = serving.DecodeEngine(adapter(), slots=paged_streams,
                                   paged=True, num_blocks=pool_blocks,
                                   disaggregate=True, name="gen",
                                   auto_start=False, **gen_cfg)
    try:
        # construction runs the scope-overlap proof over BOTH program
        # families (decode step + per-bucket prefill) and certifies
        # each; a VerifyError here IS the gate firing
        dis_server = serving.PredictorServer({"gen": dis_eng},
                                             auto_start=False)
        if not all(c.ok for c in dis_server.certificates.values()):
            errors.append("disagg zero-sync certificate failed: %s"
                          % dis_server.certificates)
        dis_eng.start()
        dis_toks, _lats, _hw = run_streams(dis_eng)
        from paddle_tpu.observability import metrics as om
        handoffs = om.counter("serving_kv_handoffs_total",
                              tenant="gen").value
    finally:
        dis_eng.close()
    if dis_toks != ring_toks:
        errors.append("disaggregated engine diverged from the ring "
                      "path")
    print("# paged arm: %d streams vs %d ring slots at %.1f KiB "
          "cache (%.1fx, block_len %d, high-water %d), p99 %.1fms "
          "vs ring %.1fms; disagg certs %s, %d handoffs"
          % (paged_streams, ring_slots, ring_bytes / 1024.0,
             stream_ratio, bl, hw, p99(paged_lats), p99(ring_lats),
             sorted(dis_server.certificates), handoffs), flush=True)

    # speculative decoding: ngram prompt-lookup draft against the
    # single-stream paged engine — identical greedy tokens, and the
    # accept-k-at-once rounds must beat one-token-per-step tokens/sec.
    # A longer horizon than the stream arm: the ngram draft earns its
    # keep once the tiny model's greedy chain starts cycling
    spec_prompt, spec_k, spec_new = [3, 5, 7], 3, 32
    spec_cfg = dict(prompt_buckets=(bucket,),
                    config=serving.GenerationConfig(
                        max_new_tokens=spec_new))

    fluid.unique_name.switch()
    plain = serving.DecodeEngine(adapter(), slots=1, paged=True,
                                 name="plain", **spec_cfg)
    try:
        plain.submit(spec_prompt).result(timeout=120)  # warm the jit
        t0 = time.perf_counter()
        plain_toks = plain.submit(spec_prompt).result(timeout=120)[0]
        tps_plain = spec_new / (time.perf_counter() - t0)
    finally:
        plain.close()

    fluid.unique_name.switch()
    spec = serving.SpeculativeDecoder(adapter(), draft="ngram",
                                      k=spec_k, name="spec",
                                      **spec_cfg)
    try:
        spec.generate(spec_prompt)  # warm the jit
        t0 = time.perf_counter()
        spec_toks, spec_info = spec.generate(spec_prompt)
        tps_spec = spec_new / (time.perf_counter() - t0)
    finally:
        spec.close()

    if spec_toks != plain_toks:
        errors.append("speculative greedy diverged from the plain "
                      "engine")
    if tps_spec < tps_plain:
        errors.append("speculative %.1f tok/s < plain %.1f tok/s"
                      % (tps_spec, tps_plain))

    if not on_tpu:
        # CPU smoke calibration: the interpret-mode kernel never beats
        # the XLA reference off-silicon, so the honest decision is the
        # conservative default — recorded under backend=cpu so a later
        # on-chip sweep is not shadowed (satellite 1)
        import jax.numpy as jnp

        rng3 = np.random.RandomState(0)
        rows = {}

        def timed3(fn, *a):
            jax.block_until_ready(fn(*a))
            t0 = time.perf_counter()
            for _ in range(3):
                r = fn(*a)
            jax.block_until_ready(r)
            return (time.perf_counter() - t0) / 3

        kernel_fn = jax.jit(lambda q, k, v, l: fd.flash_decode(q, k, v, l))
        ref_fn = jax.jit(lambda q, k, v, l: fd.decode_reference(q, k, v, l))
        for t in (64, 128):
            q = jnp.asarray(rng3.randn(2, cfg.heads, dh), jnp.float32)
            k = jnp.asarray(rng3.randn(2, cfg.heads, t, dh), jnp.float32)
            v = jnp.asarray(rng3.randn(2, cfg.heads, t, dh), jnp.float32)
            lens = jnp.full((2,), t, jnp.int32)
            os.environ["PADDLE_TPU_PALLAS"] = "interpret"
            os.environ["PADDLE_TPU_DECODE_MIN_T"] = "1"
            try:
                ker = timed3(kernel_fn, q, k, v, lens)
            finally:
                os.environ.pop("PADDLE_TPU_PALLAS", None)
                os.environ.pop("PADDLE_TPU_DECODE_MIN_T", None)
            rows[t] = (ker, timed3(ref_fn, q, k, v, lens))
        autotune.record_decode_min_t(fd.DEFAULT_MIN_T, rows=rows,
                                     backend="cpu")
        if autotune.decode_min_t_decision() != fd.DEFAULT_MIN_T:
            errors.append("decode_min_t decision did not round-trip "
                          "through the autotune cache")
        print("# decode_min_t cpu smoke: %s -> min_t=%d (backend=cpu)"
              % ({t: (round(c * 1e6), round(b * 1e6))
                  for t, (c, b) in rows.items()}, fd.DEFAULT_MIN_T),
              flush=True)

    print(json.dumps({
        "metric": "gpt_small_paged_stream_capacity_ratio",
        "value": round(stream_ratio, 2),
        "unit": "x concurrent streams vs 2-slot ring at equal HBM "
                "(%d blocks of %d rows, %d streams, paged p99 %.1fms "
                "vs ring p99 %.1fms, bit-identical greedy, on %s)"
                % (pool_blocks, bl, paged_streams, p99(paged_lats),
                   p99(ring_lats), kind),
        "vs_baseline": round(stream_ratio / 4.0, 3),  # bar: >= 4x
    }), flush=True)
    print(json.dumps({
        "metric": "gpt_small_spec_acceptance_rate",
        "value": round(spec_info["acceptance_rate"], 4),
        "unit": "accepted/proposed (ngram k=%d draft, %d rounds for "
                "%d tokens, greedy output identical to the "
                "non-speculative engine)"
                % (spec_k, spec_info["rounds"], spec_new),
        "vs_baseline": round(spec_info["acceptance_rate"], 4),
    }), flush=True)
    print(json.dumps({
        "metric": "gpt_small_spec_tokens_per_sec",
        "value": round(tps_spec, 1),
        "unit": "tokens/sec (ngram k=%d speculation vs %.1f tok/s "
                "non-speculative, %.2fx, on %s)"
                % (spec_k, tps_plain, tps_spec / max(tps_plain, 1e-9),
                   kind),
        "vs_baseline": round(tps_spec / max(tps_plain, 1e-9), 3),
    }), flush=True)

    if errors:
        for e in errors:
            print("# DECODE GATE FAILED: %s" % e, file=sys.stderr,
                  flush=True)
        raise SystemExit(1)


def child_elastic():
    """Elastic-training recovery drill (ISSUE 12): run the chaos
    elastic scenario — 3 workers, kill one mid-run — and report
    ``elastic_recovery_ms``, the wall time from the worker-lost verdict
    to the first completed step at the shrunk world.  The chaos driver
    itself enforces the hard part (rc=0 only when every survivor covers
    every step from ONE process — re-plan, reshard and resume happened
    in-process with no restart — and the post-recovery loss curve
    matches the shrunk-world oracle); this child additionally gates on
    the journaled incident chain and on the resume event carrying the
    measured recovery latency.  vs_baseline compares against a 60s
    full-job-restart budget (kill fleet, reschedule, recompile, reload
    — the Fluid-era recovery story)."""
    import shutil
    import tempfile

    from paddle_tpu.observability.journal import read_journal
    from paddle_tpu.tools import chaos

    workdir = tempfile.mkdtemp(prefix="paddle_tpu_elastic_bench_")
    print("# elastic drill: 3 workers, worker_kill mid-run — survivors "
          "must re-plan/reshard/resume in-process", flush=True)
    try:
        rc = chaos.main(["--elastic", "--ckpt-dir", workdir])
    except SystemExit as e:  # argparse or driver bail-out
        rc = int(e.code or 0)

    telemetry = os.path.join(workdir, "telemetry")
    events = read_journal(telemetry) if os.path.isdir(telemetry) else []
    kinds = [e.get("kind") for e in events]
    resumes = [e for e in events if e.get("kind") == "resume"
               and e.get("recovery_ms") is not None]

    errors = []
    if rc != 0:
        errors.append("chaos --elastic drill failed (rc=%s) — recovery "
                      "must complete in-process, without a process "
                      "restart" % rc)
    for k in ("worker-lost", "replan", "reshard", "resume"):
        if k not in kinds:
            errors.append("journal is missing the %r incident event" % k)
    if not resumes:
        errors.append("no journaled resume event carries recovery_ms")

    recovery_ms = (max(float(e["recovery_ms"]) for e in resumes)
                   if resumes else 0.0)
    restart_budget_ms = 60000.0
    print(json.dumps({
        "metric": "elastic_recovery_ms",
        "value": round(recovery_ms, 2),
        "unit": "ms worker-lost -> first step at shrunk world, "
                "in-process (3->2 workers, %d resume events)"
                % len(resumes),
        "vs_baseline": round(restart_budget_ms / max(recovery_ms, 1e-3),
                             2),
    }), flush=True)

    if errors:
        for e in errors:
            print("# ELASTIC GATE FAILED: %s" % e, file=sys.stderr,
                  flush=True)
        raise SystemExit(1)
    shutil.rmtree(workdir, ignore_errors=True)


def child_autoscale():
    """Elastic scale-up + autoscaler gate (ISSUE 17): run the chaos
    rejoin drill — 3 workers, kill one mid-run, relaunch it with
    ``--join`` — and report ``elastic_rejoin_ms``, the wall time from
    the join request to the rejoined worker's first step at the grown
    world.  The chaos driver enforces the hard part (rc=0 only when the
    fleet grows back to the full world, every digest agrees, and the
    whole shrink->grow incident chain reads causally in ONE trace);
    this child additionally gates the journaled join events and the
    SLO policy's decision triple (overload -> grow, idle -> shrink,
    in-band -> no-op) so an autoscaler regression fails the bench even
    when the drill itself survives.  vs_baseline compares against the
    same 60s full-job-restart budget the recovery drill uses — a warm
    rejoin must beat tearing the fleet down and rescheduling."""
    import shutil
    import tempfile

    from paddle_tpu.observability.journal import read_journal
    from paddle_tpu.resilience.autoscale import (GROW, NOOP, SHRINK,
                                                 SLOPolicy)
    from paddle_tpu.tools import chaos

    workdir = tempfile.mkdtemp(prefix="paddle_tpu_autoscale_bench_")
    print("# rejoin drill: 3 workers, kill one mid-run, relaunch with "
          "--join — fleet must admit, warm up and grow back to 3",
          flush=True)
    try:
        rc = chaos.main(["--elastic", "--rejoin", "--ckpt-dir", workdir])
    except SystemExit as e:  # argparse or driver bail-out
        rc = int(e.code or 0)

    telemetry = os.path.join(workdir, "telemetry")
    events = read_journal(telemetry) if os.path.isdir(telemetry) else []
    kinds = [e.get("kind") for e in events]
    rejoins = [e for e in events if e.get("kind") == "resume"
               and e.get("rejoin_ms") is not None]

    errors = []
    if rc != 0:
        errors.append("chaos --elastic --rejoin drill failed (rc=%s) — "
                      "the killed worker must rejoin through the "
                      "admission protocol and the fleet must grow back "
                      "to the full world" % rc)
    for k in ("join-request", "admitted", "warmup", "resume"):
        if k not in kinds:
            errors.append("journal is missing the %r join event" % k)
    if not rejoins:
        errors.append("no journaled resume event carries rejoin_ms")

    rejoin_ms = (max(float(e["rejoin_ms"]) for e in rejoins)
                 if rejoins else 0.0)
    restart_budget_ms = 60000.0
    print(json.dumps({
        "metric": "elastic_rejoin_ms",
        "value": round(rejoin_ms, 2),
        "unit": "ms join-request -> first step at grown world "
                "(2->3 workers, warm-up admission, %d rejoin events)"
                % len(rejoins),
        "vs_baseline": round(restart_budget_ms / max(rejoin_ms, 1e-3),
                             2),
    }), flush=True)

    # The pure decision gate: the policy that drives the control loop
    # must map the three canonical statuses to the three verdicts.
    policy = SLOPolicy(min_world=1, max_world=8, p99_step_ms=100.0,
                       p99_latency_ms=250.0, shed_rate=0.0,
                       hysteresis=0.2, cooldown_s=0.0)
    triple = (
        ({"p99_step_ms": 400.0, "p99_serving_latency_ms": 900.0,
          "serving_shed_rate": 0.3}, GROW),
        ({"p99_step_ms": 10.0, "p99_serving_latency_ms": 20.0,
          "serving_shed_rate": 0.0, "serving_queue_depth": 0}, SHRINK),
        ({"p99_step_ms": 110.0}, NOOP),
    )
    verdicts = [(policy.decide(status, world=2).action, want)
                for status, want in triple]
    correct = all(got == want for got, want in verdicts)
    print(json.dumps({
        "metric": "autoscale_decision_correct",
        "value": 1.0 if correct else 0.0,
        "unit": "SLO policy triple: overload->grow idle->shrink "
                "in-band->no-op (got %s)"
                % ", ".join(got for got, _ in verdicts),
        "vs_baseline": 1.0 if correct else 0.0,
    }), flush=True)
    if not correct:
        errors.append("SLO policy decision triple mismatch: %s"
                      % ["%s (want %s)" % v for v in verdicts])

    if errors:
        for e in errors:
            print("# AUTOSCALE GATE FAILED: %s" % e, file=sys.stderr,
                  flush=True)
        raise SystemExit(1)
    shutil.rmtree(workdir, ignore_errors=True)


def child_lint():
    """Static-analysis CI arm (ISSUE 10): run the whole-program
    analyzer with the concurrency battery (max_in_flight=2) over every
    examples/ builder and all dist_model worker sets, and fail (exit 1)
    on ANY ERROR diagnostic — the same sweep the analyzer tests run,
    but wired into the bench harness so perf/CI runs catch analyzer or
    example regressions without waiting on the full test suite.  Emits
    ``static_lint_programs_checked`` / ``static_lint_errors`` BENCH
    lines plus per-program failure detail on stderr."""
    import paddle_tpu as fluid

    repo = os.path.dirname(os.path.abspath(__file__))
    for sub in ("examples", "tests"):
        p = os.path.join(repo, sub)
        if p not in sys.path:
            sys.path.insert(0, p)

    def example_sets():
        import bert_pretrain
        import mnist_train
        import ps_migration
        import resnet_infer
        import slim_compress

        fluid.unique_name.switch()
        main, startup, test_prog, loss, acc = mnist_train.build_program()
        yield "mnist", [(main, [loss.name, acc.name]),
                        (test_prog, [acc.name]), (startup, None)]
        fluid.unique_name.switch()
        main, startup, feeds, loss = bert_pretrain.build_program(
            tiny=True, seq_len=32)
        yield "bert-tiny", [(main, [loss.name]), (startup, None)]
        fluid.unique_name.switch()
        main, startup, loss = ps_migration.build_ctr(vocab=512)
        yield "ctr", [(main, [loss.name]), (startup, None)]
        fluid.unique_name.switch()
        main, startup, prob = resnet_infer.build_program()
        yield "resnet-eval", [(main, [prob.name]), (startup, None)]
        fluid.unique_name.switch()
        main, startup, loss, acc, prob = slim_compress.build_program()
        yield "slim", [(main, [loss.name, acc.name]), (startup, None)]

    def worker_sets():
        import dist_model

        workers, _, loss = dist_model.build_pipeline_workers()
        yield "dist-pipeline", workers, loss
        workers, _, loss = dist_model.build_dp_workers(nranks=2)
        yield "dist-dp2", workers, loss
        w0, _, loss = dist_model.build_example_dp_workers(
            "bert", nranks=8)
        yield "dist-bert-dp8", [w0], loss
        workers, _, out = dist_model.build_moe_workers(nranks=2)
        yield "dist-moe2", workers, out

    checked, errors = 0, 0
    failures = []

    def sweep(label, program, targets):
        nonlocal checked, errors
        checked += 1
        report = program.analyze(targets=targets, concurrency=True,
                                 max_in_flight=2)
        bad = list(report.errors)
        if bad:
            errors += len(bad)
            failures.append(label)
            for d in bad:
                print("LINT %s: %s" % (label, d), file=sys.stderr)

    for name, progs in example_sets():
        for i, (program, targets) in enumerate(progs):
            sweep("%s[%d]" % (name, i), program, targets)
    for name, workers, fetch in worker_sets():
        for rank, w in enumerate(workers):
            has = any(fetch in op.output_arg_names
                      for b in w.blocks for op in b.ops)
            sweep("%s[r%d]" % (name, rank), w,
                  [fetch] if has else None)

    print(json.dumps({
        "metric": "static_lint_programs_checked",
        "value": checked,
        "unit": "programs (examples + dist worker sets, "
                "concurrency@K=2)",
    }), flush=True)
    print(json.dumps({
        "metric": "static_lint_errors",
        "value": errors,
        "unit": "ERROR diagnostics (failing: %s)"
                % (", ".join(failures) or "none"),
    }), flush=True)
    if errors:
        raise SystemExit(1)


def child_planner():
    """Auto-parallelism planner A/B (ISSUE 7): search the placement
    space for the BERT trainer at the visible chip count, execute the
    planner-chosen plan against the hand-written GradAllReduce DP
    builder, and emit ``bert_base_auto_plan_speedup`` (>1 = the planner
    wins).  The measured planner-arm step time is recorded against the
    predicted one in the autotune calibration cache (the ``planner``
    family), so the next search prices against silicon instead of
    constants.

    On the CPU backend it runs BERT_TINY on a virtual mesh (the caller
    passes ``--xla_force_host_platform_device_count``), on real chips
    BERT_BASE."""
    import jax

    import paddle_tpu as fluid
    from paddle_tpu import autotune
    from paddle_tpu.executor import Scope, scope_guard
    from paddle_tpu.models import bert
    from paddle_tpu.parallel.planner import (ClusterSpec, auto_transpile,
                                             resolve_cluster_spec)
    from paddle_tpu.transpiler.collective import GradAllReduce

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    ndev = len(jax.devices())
    chips = ndev  # the CPU smoke's virtual pair comes via XLA_FLAGS
    cfg = bert.BERT_BASE if on_tpu else bert.BERT_TINY
    seq = 128 if on_tpu else 32
    batch = (8 * ndev) if on_tpu else 4 * max(ndev, 1)
    warmup, steps = (3, 20) if on_tpu else (1, 4)

    def build():
        fluid.unique_name.switch()
        main, startup, feeds, loss = bert.build_pretrain(
            cfg, seq_len=seq, lr=1e-4, train=True)
        return main, startup, loss

    spec = resolve_cluster_spec(chips=chips)
    main, startup, loss = build()
    res = auto_transpile(main, spec, startup_program=startup,
                         targets=[loss.name])
    plan = res.plan

    rng = np.random.RandomState(0)
    feed = bert.make_fake_batch(batch, seq, cfg, rng)

    def timed(run_bs, env):
        saved = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        try:
            m, s, l = build()
            exe = fluid.Executor()
            cp = fluid.CompiledProgram(m).with_data_parallel(
                loss_name=l.name, build_strategy=run_bs,
                places=jax.devices())
            with scope_guard(Scope()):
                exe.run(s)
                return _timed_steps(exe, cp, feed, l.name, warmup,
                                    steps)
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    # hand-written DP arm: GradAllReduce semantics through the SPMD
    # runner at default knobs (the pre-planner user journey); the
    # explicit transpile below only prices the static twin
    hand_prog, hand_startup, hand_loss = build()
    GradAllReduce().transpile(program=hand_prog,
                              startup_program=hand_startup,
                              rank=0, nranks=chips)
    hand_prog._num_trainers = chips
    from paddle_tpu.parallel.planner import price_worker_set

    _, hand_price = price_worker_set([hand_prog], spec,
                                     targets=[hand_loss.name])
    hand_t = timed(fluid.BuildStrategy(), {})

    # measured arm: the planner's dp-family stand-in (the SAME policy
    # apply_plan uses — dp rides the SPMD runner single-process; a
    # pipeline winner needs the per-stage deployment harness, so its
    # line stays predicted-only while the dp arm still measures the
    # planner's knob choices)
    from paddle_tpu.parallel.planner import select_dp_standin

    exec_pc = select_dp_standin(res)
    if exec_pc is not None:
        exec_bs = fluid.BuildStrategy()
        exec_bs.shard_optimizer_state = exec_pc.candidate.zero1
        exec_env = {}
        if exec_pc.candidate.bucket_mb:
            exec_env["PADDLE_TPU_ALLREDUCE_BUCKET_MB"] = str(
                exec_pc.candidate.bucket_mb)
        plan_t = timed(exec_bs, exec_env)
    else:
        plan_t = None
    executable = exec_pc is not None and exec_pc is plan

    dev_name = jax_backend_name()
    speedup = (hand_t / plan_t) if plan_t else 0.0
    measured_ms = (plan_t / steps * 1000.0) if plan_t else None
    predicted_ms = (exec_pc.price.step_ms if exec_pc is not None
                    else plan.price.step_ms)
    print(json.dumps({
        "metric": "bert_base_auto_plan_speedup",
        "value": round(speedup, 4),
        "unit": "x (hand DP step time / planner plan, %s seq%d bs%d "
                "x%d chips, %d steps on %s%s)"
                % ("bert_base" if on_tpu else "bert_tiny", seq, batch,
                   ndev, steps, dev_name,
                   "" if executable else "; overall winner %s not "
                   "executable single-process — measured arm is the "
                   "cheapest dp-family candidate"
                   % plan.candidate.kind),
        "plan": plan.candidate.describe(),
        "executed_plan": exec_pc.candidate.describe()
        if exec_pc is not None else None,
        "predicted_step_ms": round(predicted_ms, 4),
        "winner_predicted_step_ms": round(plan.price.step_ms, 4),
        "measured_step_ms": round(measured_ms, 4) if measured_ms
        else None,
        "hand_predicted_step_ms": round(hand_price.step_ms, 4),
        "vs_baseline": round(speedup, 3),
    }), flush=True)

    if measured_ms and predicted_ms > 0:
        # the measure-and-learn feedback: measured vs the RAW static
        # prediction.  predicted_ms already carries the prior cached
        # factor (price_plan multiplies it in), so divide it back out —
        # recording measured/predicted as-is would make the factor
        # oscillate between f and 1.0 on alternate runs instead of
        # converging
        sig = autotune.sweep_signature(
            "planner", {"model": "bert_base" if on_tpu else "bert_tiny",
                        "chips": chips})
        prior = exec_pc.price.calibration or 1.0
        factor = measured_ms * prior / predicted_ms
        autotune.record(sig, {"calibration": factor,
                              "predicted_ms": round(predicted_ms, 4),
                              "measured_ms": round(measured_ms, 4)})
        # the family-level signature price_plan() consults
        autotune.record(autotune.sweep_signature("planner", {}),
                        {"calibration": factor})
        print(json.dumps({
            "metric": "planner_calibration_factor",
            "value": round(factor, 4),
            "unit": "measured/predicted step time (planner family, %s)"
                    % dev_name,
        }), flush=True)


def child_quant():
    """Block-quantized collective A/B (ISSUE 15): the BERT trainer's
    gradient allreduce ring dense vs int8 block-quantized.

    Two gates:

    * ``bert_base_allreduce_byte_cut`` — the analyzer-priced ICI bytes
      of the dense fused ring divided by the quantized ring's (int8
      payload + f32-per-block scale sidecar), on the SAME transpiled
      program.  Must be >= 1.8 (the int8-vs-bf16 wire math promises
      ~1.97x at block 256; the sidecar and padding eat the rest).
    * ``bert_base_quant_loss_delta`` — twin short training runs through
      the REAL executor collectives on the visible mesh (CPU smoke: the
      driver's 2 virtual devices), quant engaged vs the dense ring, same
      seeds and feeds.  Max per-step loss delta must stay <= 1e-3: the
      documented error model at training lr is noise, not drift.

    The measured-vs-model quantization error of the actual gradient
    buckets is recorded in the autotune ``quant`` family, which clears
    the ``quantizable-bucket-not-quantized`` advisory's "uncalibrated"
    tag for these shapes."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import autotune
    from paddle_tpu.executor import Scope, scope_guard
    from paddle_tpu.models import bert
    from paddle_tpu.quant import (block_dequantize, block_quantize,
                                  predicted_rms_error, quant_block)
    from paddle_tpu.static_analysis.cost import estimate_cost
    from paddle_tpu.static_analysis.fusion import resolve_fused_program
    from paddle_tpu.transpiler.collective import GradAllReduce

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    ndev = len(jax.devices())
    nranks = ndev if ndev > 1 else 2
    cfg = bert.BERT_BASE if on_tpu else bert.BERT_TINY
    seq = 128 if on_tpu else 32
    batch = (8 * ndev) if on_tpu else 2 * max(ndev, 1)
    model_name = "bert_base" if on_tpu else "bert_tiny"
    dev_name = jax_backend_name()

    def build():
        fluid.unique_name.switch()
        main, startup, feeds, loss = bert.build_pretrain(
            cfg, seq_len=seq, lr=1e-4, train=True)
        return main, startup, feeds, loss

    quant_env = {"PADDLE_TPU_QUANT": "1",
                 "PADDLE_TPU_QUANT_MIN_BYTES": "1"}
    dense_env = {"PADDLE_TPU_QUANT": "0"}
    saved = {k: os.environ.get(k) for k in
             set(quant_env) | set(dense_env)}

    def with_env(env, fn):
        os.environ.update(env)
        try:
            return fn()
        finally:
            for k in env:
                v = saved.get(k)
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    # ---- arm 1: analyzer-priced wire bytes on the transpiled twin ----
    main, startup, feeds, loss = build()
    GradAllReduce().transpile(program=main, startup_program=startup,
                              rank=0, nranks=nranks)
    main._num_trainers = nranks

    def ici_bytes(env):
        def run():
            fused, _ = resolve_fused_program(main, targets=[loss.name])
            report = estimate_cost(fused, nranks=nranks,
                                   targets=[loss.name])
            return report.total_ici_bytes
        return with_env(env, run)

    dense_ici = ici_bytes(dense_env)
    quant_ici = ici_bytes(quant_env)
    byte_cut = (dense_ici / quant_ici) if quant_ici else 0.0
    print(json.dumps({
        "metric": "bert_base_allreduce_byte_cut",
        "value": round(byte_cut, 4),
        "unit": "x dense/quant ICI bytes (%s seq%d x%d ranks, block %d, "
                "analyzer-priced, %s)"
                % (model_name, seq, nranks, quant_block(), dev_name),
        "dense_ici_bytes": int(dense_ici),
        "quant_ici_bytes": int(quant_ici),
        "vs_baseline": round(byte_cut, 3),
    }), flush=True)
    if byte_cut < 1.8:
        print("# FAIL: allreduce byte cut %.3f < 1.8 gate" % byte_cut,
              flush=True)

    # ---- autotune 'quant' family: measured error vs the model on the
    # actual quantized buckets (keyed the way the advisory looks up) ---
    rng = np.random.RandomState(0)
    blk = quant_block()
    recorded = 0
    fused_q, _ = with_env(
        quant_env,
        lambda: resolve_fused_program(main, targets=[loss.name]))
    for block in fused_q.blocks:
        for op in block.ops:
            if op.type != "c_allreduce_quant" or recorded >= 4:
                continue
            numel = 0
            for name in op.input("X"):
                v = block._find_var_recursive(name)
                if v is None or not v.shape or any(
                        d is None or d < 0 for d in v.shape):
                    continue
                n = 1
                for d in v.shape:
                    n *= d
                numel += n
            if not numel:
                continue
            g = jnp.asarray(
                rng.randn(numel).astype("float32") * 1e-2)
            q, s = block_quantize(g)
            err = g - block_dequantize(q, s, size=numel)
            measured = float(jnp.sqrt(jnp.mean(err ** 2)))
            predicted = float(predicted_rms_error(s))
            factor = measured / predicted if predicted else 1.0
            nblocks = max(numel // blk, 1)
            autotune.record(
                autotune.sweep_signature(
                    "quant", {"nblocks": nblocks, "block": blk}),
                {"calibration": round(factor, 4),
                 "measured_rms": measured,
                 "predicted_rms": predicted})
            recorded += 1
    if recorded:
        print("# quant family calibrated: %d bucket signatures" %
              recorded, flush=True)

    # ---- arm 2: twin training through the transpiled collectives ----
    # The executor's with_data_parallel path is GSPMD (XLA inserts the
    # ring; framework collective ops are identity there), so the
    # executable quantized wire lives where the transpiled programs run:
    # per-worker op interpretation under shard_map with a collective
    # axis — the same path the multi-process fleet runtime drives.  The
    # twins share seeds, batches and the transpile; only the fusion
    # rewrite differs (c_fused_allreduce_sum vs c_allreduce_quant).
    if ndev < 2:
        print("# quant loss-delta arm skipped: needs >=2 devices "
              "(driver passes --xla_force_host_platform_device_count)",
              flush=True)
        return
    from jax.sharding import Mesh, PartitionSpec as P

    from paddle_tpu.executor import _run_ops_into_env, global_scope
    from paddle_tpu.jax_compat import shard_map
    from paddle_tpu.ops import registry as op_registry

    steps = 6
    feats, hidden = 16, 64
    half = 8

    def twin_losses(env):
        def run():
            fluid.unique_name.switch()
            m, s = fluid.Program(), fluid.Program()
            m.random_seed = s.random_seed = 77
            with fluid.program_guard(m, s):
                x = fluid.layers.data("x", shape=[feats],
                                      dtype="float32")
                y = fluid.layers.data("y", shape=[1], dtype="float32")
                h = fluid.layers.fc(x, size=hidden, act="relu")
                p = fluid.layers.fc(h, size=1)
                l = fluid.layers.reduce_mean(
                    fluid.layers.square(p - y))
                fluid.optimizer.SGD(learning_rate=1e-2).minimize(l)
            GradAllReduce().transpile(program=m, startup_program=s,
                                      rank=0, nranks=2)
            m._num_trainers = 2
            fused, _ = resolve_fused_program(m, targets=[l.name])
            fblock = fused.global_block()
            kinds = [op.type for op in fblock.ops
                     if "allreduce" in op.type]
            exe = fluid.Executor()
            with scope_guard(Scope()):
                exe.run(s)
                params = {}
                for v in m.list_vars():
                    if not v.persistable:
                        continue
                    val = global_scope().get(v.name)
                    if val is not None:
                        params[v.name] = np.asarray(val)
            pnames = sorted(params)
            mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))

            def per_worker(pvals, xb, yb):
                ctx = op_registry.LoweringContext(mode="train")
                ctx.collective_axis = "dp"
                envd = {n: v[0] for n, v in zip(pnames, pvals)}
                envd["x"], envd["y"] = xb[0], yb[0]
                _run_ops_into_env(fblock, envd, ctx)
                return ([envd[n][None] for n in pnames],
                        envd[l.name].reshape(1))

            step_fn = jax.jit(shard_map(
                per_worker, mesh=mesh,
                in_specs=([P("dp")] * len(pnames), P("dp"), P("dp")),
                out_specs=([P("dp")] * len(pnames), P("dp"))))
            lrng = np.random.RandomState(4321)
            vals = [np.tile(params[n][None], (2,) + (1,) * params[n].ndim)
                    for n in pnames]
            out = []
            for _ in range(steps):
                xb = lrng.randn(2, half, feats).astype("float32")
                yb = (xb.mean(axis=2, keepdims=True)
                      + 0.05 * lrng.randn(2, half, 1)).astype("float32")
                vals, lv = step_fn([jnp.asarray(v) for v in vals],
                                   jnp.asarray(xb), jnp.asarray(yb))
                vals = [np.asarray(v) for v in vals]
                out.append(float(np.mean(np.asarray(lv))))
            return out, kinds
        return with_env(env, run)

    dense_losses, dense_kinds = twin_losses(dense_env)
    quant_losses, quant_kinds = twin_losses(quant_env)
    if not any(k == "c_allreduce_quant" for k in quant_kinds):
        raise SystemExit("quant arm vacuous: fusion emitted %r, no "
                         "c_allreduce_quant" % (quant_kinds,))
    if any(k == "c_allreduce_quant" for k in dense_kinds):
        raise SystemExit("dense arm contaminated: %r" % (dense_kinds,))
    delta = max(abs(a - b) for a, b in zip(dense_losses, quant_losses))
    print(json.dumps({
        "metric": "quant_collective_loss_delta",
        "value": round(delta, 6),
        "unit": "max |loss_quant - loss_dense| over %d DP steps on a "
                "2-worker mesh (%s ring vs %s, %s; gate <= 1e-3)"
                % (steps, "/".join(sorted(set(quant_kinds))),
                   "/".join(sorted(set(dense_kinds))), dev_name),
        "dense_losses": [round(x, 6) for x in dense_losses],
        "quant_losses": [round(x, 6) for x in quant_losses],
        "vs_baseline": 1.0 if delta <= 1e-3 else 0.0,
    }), flush=True)
    if delta > 1e-3:
        print("# FAIL: quant twin loss delta %.2e > 1e-3 gate" % delta,
              flush=True)


def child_overlap():
    """Overlap-scheduler A/B (ISSUE 16): the BERT trainer's bucketed
    gradient allreduce ring synchronous vs start/wait split.

    Two gates:

    * ``bert_overlap_exposed_wire_cut`` — the analyzer-priced
      ``exposed_wire_ms`` of the overlap schedule vs the synchronous
      one, SAME transpiled program, on an ICI-starved ClusterSpec
      where the wire dominates.  Must cut >= 25%; both provers (PR-3
      deadlock, PR-10 in-flight race) must PASS on the rewritten
      program or the metric reports proofs=FAIL.
    * ``overlap_collective_loss_delta`` — twin short training runs
      through the REAL start/wait collectives on a 2-worker shard_map
      mesh (the with_data_parallel path is GSPMD where framework
      collectives are identity — same reasoning as child_quant's
      arm 2), overlap on vs off, same seeds and feeds.  The pair is
      bit-exact with the fused op by construction, so the gate is
      BIT-IDENTICAL losses (delta == 0.0), not a tolerance."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.models import bert
    from paddle_tpu.static_analysis.concurrency import \
        find_overlap_window_races
    from paddle_tpu.static_analysis.cost import estimate_cost, price_plan
    from paddle_tpu.static_analysis.distributed import prove_deadlock_free
    from paddle_tpu.static_analysis.fusion import resolve_fused_program
    from paddle_tpu.transpiler.collective import GradAllReduce

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    ndev = len(jax.devices())
    nranks = ndev if ndev > 1 else 2
    cfg = bert.BERT_BASE if on_tpu else bert.BERT_TINY
    seq = 128 if on_tpu else 32
    model_name = "bert_base" if on_tpu else "bert_tiny"
    dev_name = jax_backend_name()
    # ICI-starved spec: wire comparable to the backward's compute so
    # hoisted windows can actually hide it (cap chosen so bert's grads
    # split into several buckets, each closing well before the
    # optimizer reads it)
    if on_tpu:
        bucket_cap, price_kw = "8", {
            "peak_tflops": 1.0, "hbm_gbps": 100.0, "ici_gbps": 10.0,
            "launch_us": 1.0}
    else:
        bucket_cap, price_kw = "0.5", {
            "peak_tflops": 0.005, "hbm_gbps": 5.0, "ici_gbps": 0.5,
            "launch_us": 1.0}

    overlap_env = {"PADDLE_TPU_OVERLAP": "1",
                   "PADDLE_TPU_ALLREDUCE_BUCKET_MB": bucket_cap}
    sync_env = {"PADDLE_TPU_OVERLAP": "0",
                "PADDLE_TPU_ALLREDUCE_BUCKET_MB": bucket_cap}
    saved = {k: os.environ.get(k) for k in
             set(overlap_env) | set(sync_env)}

    def with_env(env, fn):
        os.environ.update(env)
        try:
            return fn()
        finally:
            for k in env:
                v = saved.get(k)
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    # ---- arm 1: analyzer-priced exposed wire + both proofs ----------
    fluid.unique_name.switch()
    main, startup, feeds, loss = bert.build_pretrain(
        cfg, seq_len=seq, lr=1e-4, train=True)
    GradAllReduce().transpile(program=main, startup_program=startup,
                              rank=0, nranks=nranks)
    main._num_trainers = nranks

    def priced(env):
        def run():
            fused, _ = resolve_fused_program(main, targets=[loss.name])
            report = estimate_cost(fused, nranks=nranks,
                                   targets=[loss.name])
            return fused, price_plan(report, **price_kw).to_dict()
        return with_env(env, run)

    fused_ov, price_ov = priced(overlap_env)
    _, price_sync = priced(sync_env)
    exposed_on = price_ov["exposed_wire_ms"]
    exposed_off = price_sync["exposed_wire_ms"]
    cut = (1.0 - exposed_on / exposed_off) if exposed_off else 0.0

    ov_report = getattr(fused_ov, "_overlap_report", None)
    applied = len(ov_report.applied) if ov_report else 0
    race_diags = find_overlap_window_races(fused_ov)
    _, dl_diags = prove_deadlock_free([fused_ov] * nranks,
                                      nranks=nranks)
    proofs_ok = (applied > 0 and not race_diags
                 and not [d for d in dl_diags
                          if d.severity.name == "ERROR"])
    print(json.dumps({
        "metric": "bert_overlap_exposed_wire_cut",
        "value": round(cut, 4),
        "unit": "1 - exposed_wire_ms(overlap)/exposed_wire_ms(sync) "
                "(%s seq%d x%d ranks, bucket %sMB, ICI-starved spec, "
                "analyzer-priced, %s; gate >= 0.25)"
                % (model_name, seq, nranks, bucket_cap, dev_name),
        "exposed_ms_overlap": round(exposed_on, 4),
        "exposed_ms_sync": round(exposed_off, 4),
        "overlap_fraction": price_ov["overlap_fraction"],
        "windows_applied": applied,
        "proofs": "PASS" if proofs_ok else "FAIL",
        "vs_baseline": round(cut, 3),
    }), flush=True)
    if cut < 0.25:
        print("# FAIL: exposed wire cut %.3f < 0.25 gate" % cut,
              flush=True)
    if not proofs_ok:
        print("# FAIL: overlap proofs did not pass (applied=%d, "
              "races=%d, deadlock diags=%d)"
              % (applied, len(race_diags), len(dl_diags)), flush=True)

    # ---- arm 2: twin training through the real start/wait pair ------
    if ndev < 2:
        print("# overlap loss-delta arm skipped: needs >=2 devices "
              "(driver passes --xla_force_host_platform_device_count)",
              flush=True)
        return
    from jax.sharding import Mesh, PartitionSpec as P

    from paddle_tpu.executor import (Scope, _run_ops_into_env,
                                     global_scope, scope_guard)
    from paddle_tpu.jax_compat import shard_map
    from paddle_tpu.ops import registry as op_registry

    steps = 6
    feats, hidden = 16, 64
    half = 8

    def twin_losses(env):
        def run():
            fluid.unique_name.switch()
            m, s = fluid.Program(), fluid.Program()
            m.random_seed = s.random_seed = 77
            with fluid.program_guard(m, s):
                x = fluid.layers.data("x", shape=[feats],
                                      dtype="float32")
                y = fluid.layers.data("y", shape=[1], dtype="float32")
                h = fluid.layers.fc(x, size=hidden, act="relu")
                h2 = fluid.layers.fc(h, size=hidden, act="relu")
                p = fluid.layers.fc(h2, size=1)
                l = fluid.layers.reduce_mean(
                    fluid.layers.square(p - y))
                fluid.optimizer.SGD(learning_rate=1e-2).minimize(l)
            GradAllReduce().transpile(program=m, startup_program=s,
                                      rank=0, nranks=2)
            m._num_trainers = 2
            fused, _ = resolve_fused_program(m, targets=[l.name])
            fblock = fused.global_block()
            kinds = [op.type for op in fblock.ops
                     if "allreduce" in op.type]
            exe = fluid.Executor()
            with scope_guard(Scope()):
                exe.run(s)
                params = {}
                for v in m.list_vars():
                    if not v.persistable:
                        continue
                    val = global_scope().get(v.name)
                    if val is not None:
                        params[v.name] = np.asarray(val)
            pnames = sorted(params)
            mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))

            def per_worker(pvals, xb, yb):
                ctx = op_registry.LoweringContext(mode="train")
                ctx.collective_axis = "dp"
                envd = {n: v[0] for n, v in zip(pnames, pvals)}
                envd["x"], envd["y"] = xb[0], yb[0]
                _run_ops_into_env(fblock, envd, ctx)
                return ([envd[n][None] for n in pnames],
                        envd[l.name].reshape(1))

            step_fn = jax.jit(shard_map(
                per_worker, mesh=mesh,
                in_specs=([P("dp")] * len(pnames), P("dp"), P("dp")),
                out_specs=([P("dp")] * len(pnames), P("dp"))))
            lrng = np.random.RandomState(4321)
            vals = [np.tile(params[n][None], (2,) + (1,) * params[n].ndim)
                    for n in pnames]
            out = []
            for _ in range(steps):
                xb = lrng.randn(2, half, feats).astype("float32")
                yb = (xb.mean(axis=2, keepdims=True)
                      + 0.05 * lrng.randn(2, half, 1)).astype("float32")
                vals, lv = step_fn([jnp.asarray(v) for v in vals],
                                   jnp.asarray(xb), jnp.asarray(yb))
                vals = [np.asarray(v) for v in vals]
                out.append(float(np.mean(np.asarray(lv))))
            return out, kinds
        return with_env(env, run)

    twin_env_on = dict(overlap_env,
                       PADDLE_TPU_ALLREDUCE_BUCKET_MB="0.004")
    twin_env_off = dict(sync_env,
                        PADDLE_TPU_ALLREDUCE_BUCKET_MB="0.004")
    ov_losses, ov_kinds = twin_losses(twin_env_on)
    sync_losses, sync_kinds = twin_losses(twin_env_off)
    if not any(k == "c_allreduce_start" for k in ov_kinds):
        raise SystemExit("overlap arm vacuous: fusion emitted %r, no "
                         "c_allreduce_start" % (ov_kinds,))
    if any(k in ("c_allreduce_start", "c_allreduce_wait")
           for k in sync_kinds):
        raise SystemExit("sync arm contaminated: %r" % (sync_kinds,))
    delta = max(abs(a - b) for a, b in zip(sync_losses, ov_losses))
    bitmatch = sync_losses == ov_losses
    print(json.dumps({
        "metric": "overlap_collective_loss_delta",
        "value": round(delta, 10),
        "unit": "max |loss_overlap - loss_sync| over %d DP steps on a "
                "2-worker mesh (%s vs %s, %s; gate == 0.0 bit-exact)"
                % (steps, "/".join(sorted(set(ov_kinds))),
                   "/".join(sorted(set(sync_kinds))), dev_name),
        "sync_losses": [repr(x) for x in sync_losses],
        "overlap_losses": [repr(x) for x in ov_losses],
        "bit_identical": bool(bitmatch),
        "vs_baseline": 1.0 if bitmatch else 0.0,
    }), flush=True)
    if not bitmatch:
        print("# FAIL: overlap twin losses not bit-identical "
              "(max delta %.3e)" % delta, flush=True)


def child_hierarchy():
    """Hierarchical-collective A/B (ISSUE 18): the BERT trainer's
    gradient ring flat across a virtual 2-tier mesh (chips=8 in 2
    slices, DCN between them) vs the reduce-scatter / cross-slice
    allreduce / allgather decomposition with the DCN hop
    int8-quantized.

    Two gates:

    * ``bert_base_slow_tier_byte_cut`` — the analyzer-priced DCN-tier
      wire bytes of the flat fused ring divided by the hierarchical +
      per-tier-int8 schedule's, on the SAME transpiled program.  The
      tier math promises ~2(n-1)/n : 2(1/c)(s-1)/s = 7x at c=4, s=2
      before quantization; the gate is >= 1.8.
    * ``hierarchy_collective_loss_delta`` — twin short training runs
      through the REAL decomposed collectives on a 4-worker shard_map
      mesh (2 slices x 2 chips, the virtual 2-tier mesh), hierarchy
      engaged vs the flat ring, same seeds and feeds.  The float-sum
      decomposition is order-fixed (ascending slice), so the losses
      must match the flat schedule BIT-EXACTLY."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.executor import Scope, scope_guard
    from paddle_tpu.models import bert
    from paddle_tpu.parallel.planner import ClusterSpec
    from paddle_tpu.static_analysis.cost import estimate_cost
    from paddle_tpu.static_analysis.fusion import resolve_fused_program
    from paddle_tpu.transpiler.collective import GradAllReduce

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    ndev = len(jax.devices())
    cfg = bert.BERT_BASE if on_tpu else bert.BERT_TINY
    seq = 128 if on_tpu else 32
    model_name = "bert_base" if on_tpu else "bert_tiny"
    dev_name = jax_backend_name()
    spec = {"chips": 8, "slices": 2, "ici_gbps": 1200.0,
            "dcn_gbps": 25.0, "launch_us": 5.0, "dcn_launch_us": 50.0}
    cluster = ClusterSpec.coerce(spec)
    nranks = cluster.chips

    flat_env = {"PADDLE_TPU_HIERARCHY": "0", "PADDLE_TPU_QUANT": "0"}
    hier_env = {"PADDLE_TPU_HIERARCHY": "1", "PADDLE_TPU_QUANT": "1",
                "PADDLE_TPU_QUANT_MIN_BYTES": "1"}
    saved = {k: os.environ.get(k) for k in
             set(flat_env) | set(hier_env)}

    def with_env(env, fn):
        os.environ.update(env)
        try:
            return fn()
        finally:
            for k in env:
                v = saved.get(k)
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    # ---- arm 1: analyzer-priced slow-tier bytes on the 2-tier twin --
    fluid.unique_name.switch()
    main, startup, feeds, loss = bert.build_pretrain(
        cfg, seq_len=seq, lr=1e-4, train=True)
    GradAllReduce().transpile(program=main, startup_program=startup,
                              rank=0, nranks=nranks)
    main._num_trainers = nranks
    main._cluster_spec = dict(spec)

    def dcn_bytes(env):
        def run():
            fused, _ = resolve_fused_program(main, targets=[loss.name])
            report = estimate_cost(fused, nranks=nranks,
                                   targets=[loss.name])
            return report.ici_bytes_per_tier(cluster).get("dcn", 0)
        return with_env(env, run)

    flat_dcn = dcn_bytes(flat_env)
    hier_dcn = dcn_bytes(hier_env)
    byte_cut = (flat_dcn / hier_dcn) if hier_dcn else 0.0
    print(json.dumps({
        "metric": "bert_base_slow_tier_byte_cut",
        "value": round(byte_cut, 4),
        "unit": "x flat/hierarchical DCN-tier bytes (%s seq%d, "
                "chips=%d in %d slices, per-tier int8 on the cross "
                "hop, analyzer-priced, %s; gate >= 1.8)"
                % (model_name, seq, nranks, cluster.slices, dev_name),
        "flat_dcn_bytes": int(flat_dcn),
        "hier_dcn_bytes": int(hier_dcn),
        "vs_baseline": round(byte_cut, 3),
    }), flush=True)
    if byte_cut < 1.8:
        print("# FAIL: slow-tier byte cut %.3f < 1.8 gate" % byte_cut,
              flush=True)

    # ---- arm 2: twin training through the decomposed collectives ----
    # 4 workers = 2 slices x 2 chips: the smallest mesh where both the
    # intra-slice reduce-scatter/allgather AND the cross-slice hop are
    # real collectives.  GSPMD with_data_parallel is identity here, so
    # the twins run per-worker op interpretation under shard_map — the
    # same path the multi-process fleet runtime drives.
    if ndev < 4:
        print("# hierarchy loss-delta arm skipped: needs >=4 devices "
              "(driver passes --xla_force_host_platform_device_count)",
              flush=True)
        return
    from jax.sharding import Mesh, PartitionSpec as P

    from paddle_tpu.executor import _run_ops_into_env, global_scope
    from paddle_tpu.jax_compat import shard_map
    from paddle_tpu.ops import registry as op_registry

    steps = 6
    feats, hidden = 16, 64
    half = 8
    nw = 4

    def twin_losses(hier):
        def run():
            fluid.unique_name.switch()
            m, s = fluid.Program(), fluid.Program()
            m.random_seed = s.random_seed = 77
            with fluid.program_guard(m, s):
                x = fluid.layers.data("x", shape=[feats],
                                      dtype="float32")
                y = fluid.layers.data("y", shape=[1], dtype="float32")
                h = fluid.layers.fc(x, size=hidden, act="relu")
                p = fluid.layers.fc(h, size=1)
                l = fluid.layers.reduce_mean(
                    fluid.layers.square(p - y))
                fluid.optimizer.SGD(learning_rate=1e-2).minimize(l)
            GradAllReduce().transpile(program=m, startup_program=s,
                                      rank=0, nranks=nw)
            m._num_trainers = nw
            m._hierarchy = ({"chips_per_slice": 2} if hier else False)
            fused, _ = resolve_fused_program(m, targets=[l.name])
            fblock = fused.global_block()
            kinds = [op.type for op in fblock.ops
                     if "allreduce" in op.type or "hier" in op.type]
            exe = fluid.Executor()
            with scope_guard(Scope()):
                exe.run(s)
                params = {}
                for v in m.list_vars():
                    if not v.persistable:
                        continue
                    val = global_scope().get(v.name)
                    if val is not None:
                        params[v.name] = np.asarray(val)
            pnames = sorted(params)
            mesh = Mesh(np.array(jax.devices()[:nw]), ("dp",))

            def per_worker(pvals, xb, yb):
                ctx = op_registry.LoweringContext(mode="train")
                ctx.collective_axis = "dp"
                envd = {n: v[0] for n, v in zip(pnames, pvals)}
                envd["x"], envd["y"] = xb[0], yb[0]
                _run_ops_into_env(fblock, envd, ctx)
                return ([envd[n][None] for n in pnames],
                        envd[l.name].reshape(1))

            step_fn = jax.jit(shard_map(
                per_worker, mesh=mesh,
                in_specs=([P("dp")] * len(pnames), P("dp"), P("dp")),
                out_specs=([P("dp")] * len(pnames), P("dp"))))
            lrng = np.random.RandomState(4321)
            vals = [np.tile(params[n][None],
                            (nw,) + (1,) * params[n].ndim)
                    for n in pnames]
            out = []
            for _ in range(steps):
                xb = lrng.randn(nw, half, feats).astype("float32")
                yb = (xb.mean(axis=2, keepdims=True)
                      + 0.05 * lrng.randn(nw, half, 1)).astype(
                          "float32")
                vals, lv = step_fn([jnp.asarray(v) for v in vals],
                                   jnp.asarray(xb), jnp.asarray(yb))
                vals = [np.asarray(v) for v in vals]
                out.append(float(np.mean(np.asarray(lv))))
            return out, kinds
        return with_env(flat_env if not hier
                        else {"PADDLE_TPU_HIERARCHY": "1",
                              "PADDLE_TPU_QUANT": "0"}, run)

    flat_losses, fkinds = twin_losses(False)
    hier_losses, hkinds = twin_losses(True)
    if not any("hier" in k for k in hkinds):
        raise SystemExit("hierarchy arm vacuous: fusion emitted %r, "
                         "no c_hier_* ops" % (hkinds,))
    if any("hier" in k for k in fkinds):
        raise SystemExit("flat arm contaminated: %r" % (fkinds,))
    delta = max(abs(a - b) for a, b in zip(flat_losses, hier_losses))
    bitmatch = all(repr(a) == repr(b)
                   for a, b in zip(flat_losses, hier_losses))
    print(json.dumps({
        "metric": "hierarchy_collective_loss_delta",
        "value": round(delta, 10),
        "unit": "max |loss_hier - loss_flat| over %d DP steps on a "
                "4-worker 2-slice mesh (%s vs %s, %s; gate == 0.0 "
                "bit-exact)"
                % (steps, "/".join(sorted(set(hkinds))),
                   "/".join(sorted(set(fkinds))), dev_name),
        "flat_losses": [repr(x) for x in flat_losses],
        "hier_losses": [repr(x) for x in hier_losses],
        "bit_identical": bool(bitmatch),
        "vs_baseline": 1.0 if bitmatch else 0.0,
    }), flush=True)
    if not bitmatch:
        print("# FAIL: hierarchy twin losses not bit-identical "
              "(max delta %.3e)" % delta, flush=True)


def jax_backend_name():
    import jax

    return jax.devices()[0].platform


def child_ctr():
    """DeepFM CTR with HOST-RESIDENT embedding tables (BASELINE config 5;
    the reference's pserver/distributed-lookup-table workload, here via
    paddle_tpu.host_table: per-step slab prefetch + async sparse push)."""
    import paddle_tpu as fluid
    from paddle_tpu.models import ctr

    dev = _require_chip()
    batch = 4096
    vocab = 1_000_000
    num_slots, slot_len = 8, 4
    warmup, steps = 2, 30
    main_prog, startup, feeds, loss, prob = ctr.build(
        model="deepfm", num_slots=num_slots, slot_len=slot_len,
        vocab=vocab, use_host_table=True)
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup)
    rng = np.random.RandomState(0)
    feed = {"slot_%d" % i: rng.randint(
        0, vocab, (batch, slot_len)).astype("int64")
        for i in range(num_slots)}
    feed["label"] = rng.randint(0, 2, (batch, 1)).astype("int64")
    dt = _timed_steps(exe, main_prog, feed, loss, warmup, steps)
    eps = batch * steps / dt
    print(json.dumps({
        "metric": "deepfm_host_table_train_examples_per_sec_per_chip",
        "value": round(eps, 1),
        "unit": "examples/sec/chip (V=%d host-resident tables, bs%d, %s)"
                % (vocab, batch, dev.device_kind),
        "vs_baseline": 1.0,  # functional target (no published number)
    }), flush=True)


def child_bert(seq_len=128):
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.models import bert

    dev = _require_chip()
    cfg = bert.BERT_BASE  # L12 D768 H12 FF3072 V30522
    # A/B knob: PADDLE_BENCH_FUSE_ATTN=0/1 forces the unfused op-chain
    # attention / the fused_multihead_attention op; unset keeps the
    # config default ("auto": route by seq_len vs the flash threshold —
    # the measured winner on both sides)
    if seq_len > cfg.max_seq:
        # long-context ladder (bert1024/bert2048): extend the position
        # table to the bench sequence length
        import copy

        cfg = copy.copy(cfg)
        cfg.max_seq = seq_len
    fa_env = os.environ.get("PADDLE_BENCH_FUSE_ATTN")
    if fa_env not in (None, "", "0", "1", "auto"):
        raise SystemExit("PADDLE_BENCH_FUSE_ATTN must be 0, 1 or auto, "
                         "got %r" % fa_env)
    if fa_env in ("0", "1"):
        import copy

        cfg = copy.copy(cfg)
        cfg.fuse_attn = fa_env == "1"
    # A/B knob: PADDLE_BENCH_MAX_PRED=0 → legacy all-position MLM head
    # (more vocab-matmul FLOPs, the r02 configuration); unset → the
    # masked-gather default.  MFU denominator follows the choice.
    # (Parsed here because the fused-QKV default below keys on it.)
    mp_env = os.environ.get("PADDLE_BENCH_MAX_PRED")
    max_pred = int(mp_env) if mp_env not in (None, "") else None
    # fused dropout+add+layer_norm Pallas op: measured +26% at seq128
    # on BOTH heads (gathered 176.2k vs 140.3k same-session control;
    # fullhead MFU 0.480 vs 0.421 — past the 0.45 gate) and +13/+16/
    # +10% at seq512/1024/2048, validated on chip
    # (tools/validate_fused_ln.py: mask mass, determinism, rate-0
    # parity, convergence).  Default ON; PADDLE_BENCH_FUSED_LN=0 forces
    # the three-op chain.
    fl_env = os.environ.get("PADDLE_BENCH_FUSED_LN")
    if fl_env not in (None, "", "0", "1"):
        raise SystemExit("PADDLE_BENCH_FUSED_LN must be 0 or 1, got %r"
                         % fl_env)
    use_fln = fl_env != "0"
    if use_fln:
        import copy

        cfg = copy.copy(cfg)
        cfg.fused_ln = True
    # fused-QKV: wins at seq128 on the gathered head (140.1k vs
    # 137.9k), and WITH fused-LN on the fullhead too (0.504 vs 0.480 —
    # the pre-fused-LN fullhead cliff at 53.4k was a fusion-boundary
    # artifact the fused kernel removes).  Without fused-LN the
    # fullhead cliff stands, and longer sequences measured neutral, so
    # the default keys on all three.  PADDLE_BENCH_FUSED_QKV=0/1 forces.
    fq_env = os.environ.get("PADDLE_BENCH_FUSED_QKV")
    if fq_env not in (None, "", "0", "1"):
        raise SystemExit("PADDLE_BENCH_FUSED_QKV must be 0 or 1, got %r"
                         % fq_env)
    use_qkv = (fq_env == "1") if fq_env in ("0", "1") else (
        seq_len == 128 and (use_fln or max_pred != 0))
    if use_qkv:
        import copy

        cfg = copy.copy(cfg)
        cfg.fused_qkv = True
    batch = 64 if seq_len <= 128 else 16
    bs_env = os.environ.get("PADDLE_BENCH_BERT_BS")
    if bs_env:
        batch = int(bs_env)
    # the timed window ends with one loss fetch; long enough that the
    # fetch's round trip is amortized (real training fetches rarely)
    warmup, steps = 3, 100

    main_prog, startup, feed_names, loss = bert.build_pretrain(
        cfg, seq_len=seq_len, lr=1e-4, amp=True, train=True,
        max_pred=max_pred,
    )
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup)

    # num_iteration_per_run (execution_strategy.h:42): K optimizer steps
    # per dispatch as one scanned launch — amortizes host dispatch the
    # way a real TPU training loop does.  The emitted unit string
    # records the setting.
    run_prog, steps, iters = _wrap_iters_per_run(main_prog, loss, steps)

    rng = np.random.RandomState(0)
    feed = bert.make_fake_batch(batch, seq_len, cfg, rng, max_pred=max_pred)
    # stage the batch on device once: a real input pipeline prefetches
    # batches ahead of the step (SURVEY §7 input-pipeline overlap), so the
    # timed loop should not pay per-step H2D latency for an identical batch
    feed = {k: jnp.asarray(v) for k, v in feed.items()}

    dt = _timed_steps(exe, run_prog, feed, loss, warmup, steps)

    tokens_per_sec = batch * seq_len * steps * iters / dt
    flops_per_token = model_train_flops_per_token(cfg, seq_len,
                                                  max_pred=max_pred)
    mfu = tokens_per_sec * flops_per_token / peak_flops(dev)

    if seq_len == 128:
        metric, bar = FLAGSHIP_METRIC, 0.45
    else:
        metric = "bert_base_seq%d_mlm_train_tokens_per_sec_per_chip" % seq_len
        bar = 0.40  # long-seq target (VERDICT r2 #3)
    line = {
        "metric": metric,
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec/chip (seq%d bs%d bf16 AMP%s%s, MFU %.3f on %s)"
                % (seq_len, batch,
                   " ipr%d" % iters if iters > 1 else "",
                   ("" if max_pred is None else
                    " fullhead" if max_pred == 0 else " mp%d" % max_pred)
                   + ({"auto": "", True: " fused-attn",
                       False: " unfused-attn"}[cfg.fuse_attn]),
                   mfu, dev.device_kind),
        "vs_baseline": round(mfu / bar, 3),
    }
    # measured result prints BEFORE the cross-check's AOT lower, so a
    # failure there cannot lose the number.  The enriched line
    # re-prints after (consumers read the LAST line per metric).
    print(json.dumps(line), flush=True)
    from paddle_tpu.executor import global_scope

    xla_flops = _xla_flops_per_step(global_scope(), feed)
    if xla_flops:
        line.update(_mfu_fields(mfu, steps * iters / dt, xla_flops,
                                peak_flops(dev)))
        print(json.dumps(line), flush=True)


# ---------------------------------------------------------------------------
# orchestrator (imports no jax; everything subprocessed + timed out)
# ---------------------------------------------------------------------------


def _run_child(mode, timeout_s):
    """Run ``python bench.py --child <mode>``; return (ok, json_lines, err).

    The child runs in its own session (process group) and the WHOLE group
    is SIGKILLed on timeout: a child may start helper processes that
    inherit the stdout pipe, and killing only the direct child would leave
    communicate() blocked on pipe EOF held by the orphan."""
    import signal

    try:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--child", mode],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            start_new_session=True,
        )
    except Exception as e:  # noqa: BLE001 - harness must never crash
        return False, [], "launch failed: %s" % e
    try:
        out, err = proc.communicate(timeout=timeout_s)
        rc = proc.returncode
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        try:  # group is dead → EOF arrives; bounded residual drain
            out, _ = proc.communicate(timeout=15)
        except Exception:  # noqa: BLE001
            out = ""
        return False, _json_lines(out or ""), "timeout after %ds" % timeout_s
    lines = _json_lines(out or "")
    if rc != 0:
        return False, lines, "rc=%d %s" % (rc, (err or "")[-400:].strip())
    return True, lines, ""


def _json_lines(text):
    out = []
    for ln in text.splitlines():
        ln = ln.strip()
        if ln.startswith("{"):
            try:
                out.append(json.loads(ln))
            except ValueError:
                pass
    return out


def _dedupe_metrics(lines):
    """One record per metric, LAST occurrence wins (in original order).

    The train children deliberately print their measured line BEFORE the
    MFU cross-check's AOT lower (a failure there must not lose the
    number) and re-print it enriched after — so a clean child emits the
    same ``*_per_chip`` metric twice.  The orchestrator merges them here
    so BENCH_*.json trajectories count each metric once; non-metric
    lines (probe results, compile markers) pass through untouched."""
    last = {}
    for l in lines:
        m = l.get("metric")
        if m:
            last[m] = l
    out = []
    seen = set()
    for l in lines:
        m = l.get("metric")
        if not m:
            out.append(l)
        elif m not in seen:
            seen.add(m)
            out.append(last[m])
    return out


# chip children, in priority order, with their time caps (seconds); the
# flagship runs first and its line is re-printed last
CHIP_PLAN = [("bert", 420), ("ctr", 160), ("resnet", 340),
             ("bert512", 270), ("infer", 220), ("bert_infer", 200)]
# count / bit-exactness gates and A/B drills that share the run
GATE_PLAN = [("fusion", 150), ("kernels", 220), ("planner", 220),
             ("observability", 150), ("tracing", 150), ("serving", 200),
             ("decode", 200), ("elastic", 240), ("quant", 220),
             ("overlap", 220), ("hierarchy", 220), ("autoscale", 300)]


def main():
    """Run every child, one after another, each in its own process.

    This process never imports jax: a parent that has touched JAX holds
    the chip, and a child that needs it then fails or hangs.  Exits
    non-zero when the probe finds no TPU or any child fails; nothing is
    retried, replayed from an earlier capture, or re-run on the CPU."""
    _, lines, err = _run_child("probe", PROBE_TIMEOUT_S)
    probe = next((l for l in lines if l.get("probe") == "ok"), None)
    if not probe or probe.get("platform") != "tpu":
        print("# no TPU: %s" % (err or "backend probe reported platform "
                                "%r" % (probe and probe.get("platform"))),
              file=sys.stderr, flush=True)
        return 1

    failed = []
    flagship_line = None
    for mode, cap in CHIP_PLAN + GATE_PLAN:
        w_ok, w_lines, w_err = _run_child(mode, cap)
        if not w_ok:
            print("# %s bench failed: %s" % (mode, w_err), flush=True)
            failed.append(mode)
        # every completed line prints IMMEDIATELY: a kill mid-run must
        # not lose finished results
        for l in _dedupe_metrics(w_lines):
            print(json.dumps(l), flush=True)
            if l.get("metric") == FLAGSHIP_METRIC:
                flagship_line = l
    if flagship_line is not None:
        # re-print so the flagship is also the LAST line
        print(json.dumps(flagship_line), flush=True)
    if failed or flagship_line is None:
        print("# FAILED children: %s%s"
              % (failed, "" if flagship_line else " (no flagship line)"),
              file=sys.stderr, flush=True)
        return 1
    return 0


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--child":
        mode = sys.argv[2]
        _child_setup()
        if mode == "probe":
            child_probe()
        elif mode == "resnet":
            child_resnet()
        elif mode == "ctr":
            child_ctr()
        elif mode == "bert":
            child_bert(128)
        elif mode.startswith("bert") and mode[4:].isdigit():
            # bert512 / bert1024 / bert2048 ... — the long-context
            # ladder (the flash kernel's regime from MIN_T up)
            child_bert(int(mode[4:]))
        elif mode == "infer":
            child_infer()
        elif mode == "bert_infer":
            child_bert_infer()
        elif mode == "fusion":
            child_fusion()
        elif mode == "observability":
            child_observability()
        elif mode == "tracing":
            child_tracing()
        elif mode == "kernels":
            child_kernels()
        elif mode == "planner":
            child_planner()
        elif mode == "quant":
            child_quant()
        elif mode == "overlap":
            child_overlap()
        elif mode == "hierarchy":
            child_hierarchy()
        elif mode == "serving":
            child_serving()
        elif mode == "decode":
            child_decode()
        elif mode == "elastic":
            child_elastic()
        elif mode == "autoscale":
            child_autoscale()
        elif mode == "lint":
            child_lint()
        else:
            raise SystemExit("unknown child mode %r" % mode)
        sys.exit(0)
    sys.exit(main())
