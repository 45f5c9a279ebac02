"""Capture a jax profiler trace of a bench train step (BERT default,
``--model resnet`` for the conv workload) and print the top-op time
breakdown (MFU diagnosis aid)."""
import sys

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])
sys.path.insert(0, __file__.rsplit("/", 1)[0])  # xplane_top_ops sibling

TRACE_DIR = "/tmp/bench_trace"


def run_and_trace(cfg_kw=None, batch=64, seq_len=128, steps=5):
    import paddle_tpu as fluid
    from paddle_tpu.models import bert
    from paddle_tpu.executor import Scope, scope_guard

    if cfg_kw:
        cfg = bert.BertConfig(**cfg_kw)
    else:
        # trace the SHIPPED flagship config (bench.py child_bert
        # defaults): fused-LN glue + fused-QKV projections
        cfg = bert.BertConfig(fused_ln=True, fused_qkv=True)
    main_prog, startup, _, loss = bert.build_pretrain(
        cfg, seq_len=seq_len, lr=1e-4, amp=True, train=True
    )
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        rng = np.random.RandomState(0)
        feed = bert.make_fake_batch(batch, seq_len, cfg, rng)
        _trace_loop(exe, main_prog, feed, loss, steps)


def run_and_trace_resnet(batch=64, steps=5):
    """ResNet-50 imagenet AMP train-step trace — the bs64 bench
    configuration (mfu_xla 0.30 in r05 window 2: where do the other 70
    points go?).  PADDLE_BENCH_RESNET_FMT=NHWC profiles the
    channels-last variant."""
    import os

    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.models import resnet
    from paddle_tpu.executor import Scope, scope_guard

    fmt = os.environ.get("PADDLE_BENCH_RESNET_FMT", "NCHW").upper()
    size = 224
    main_prog, startup, _, loss, _ = resnet.build(
        dataset="imagenet", amp=True, data_format=fmt)
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
        _trace_loop(exe, main_prog, feed, loss, steps)


def _trace_loop(exe, prog, feed, loss, steps):
    """The shared trace protocol: 3 warmups, a fetch-synced step (the
    loss fetch blocks until the device drains — compile + ramp-up stay
    out of the trace), then `steps` traced dispatches ending on another
    fetch-sync so the final step's device work is inside the window."""
    import jax

    for _ in range(3):
        exe.run(prog, feed=feed, fetch_list=[])
    exe.run(prog, feed=feed, fetch_list=[loss])
    jax.profiler.start_trace(TRACE_DIR)
    for _ in range(steps - 1):
        exe.run(prog, feed=feed, fetch_list=[])
    exe.run(prog, feed=feed, fetch_list=[loss])
    jax.profiler.stop_trace()


def _category(name):
    """Op name → optimization category.  Explicit matching, not loose
    substrings: 'convert' must not bin as conv, 'reduce_sum' is not the
    grad-aggregation 'sum' op, 'elementwise_mul' is not a matmul.
    Plain 'matmul' is deliberately matmul/conv, NOT attention — the MLM
    vocab projection shares the op type with attention scores, and the
    per-op table cannot tell instances apart; attention here means the
    unambiguous fused/softmax paths only."""
    import re as _re

    from paddle_tpu.profiler import ASYNC_OVERLAP_ROW

    if name == ASYNC_OVERLAP_ROW:
        return "async-overlap"
    n = _re.sub(r"\.\d+$", "", name.lstrip("~"))
    # a backward op optimizes the same lever as its forward (mul_grad
    # is fc matmuls, layer_norm_grad is norm, ...) — bin by base type
    n = _re.sub(r"_grad$", "", n)
    if "cross_entropy" in n or "label_smooth" in n:
        return "loss"
    if "multihead" in n or "flash" in n or n == "softmax":
        return "attention"
    if n.startswith("fused_dropout_add_ln"):
        # the fused glue kernel carries dropout+residual+LN — its own
        # bucket, not "dropout" (which would overstate dropout 4x)
        return "fused-ln-glue"
    if n in ("sum", "scale") or any(
            k in n for k in ("adam", "sgd", "momentum", "lamb", "clip")):
        return "optimizer"
    if n.endswith("_norm") or "_norm_" in n:
        return "norm"
    if "dropout" in n:
        return "dropout"
    if n in ("mul", "fc") or "matmul" in n or n.startswith(
            ("conv2d", "conv3d", "depthwise_conv", "lookup", "gather",
             "embedding")):
        return "matmul/conv"
    if n.startswith(("elementwise", "cast", "convert", "relu", "gelu",
                     "tanh", "reshape", "transpose")) or n == "add":
        return "elementwise"
    return "other"


def _categorize(table):
    """Grep-able CATEGORY lines: one glance at the captured artifact
    names the biggest lever."""
    cats = {}
    total = 0.0
    for name, (calls, tot, mx, mn) in table.items():
        # device_op_stats keys are BARE op types (attribute_op_name
        # strips the pd<i>_ scope prefix): 'layer_norm', 'matmul', ...
        cat = _category(name)
        cats[cat] = cats.get(cat, 0.0) + tot
        if cat != "async-overlap":
            total += tot  # async spans overlap compute: not wall time
    for cat, t in sorted(cats.items(), key=lambda kv: -kv[1]):
        if cat == "async-overlap":
            print("CATEGORY %-14s %10.3f ms  (in-flight, overlaps the "
                  "rows above; excluded from %%)" % (cat, t), flush=True)
        else:
            print("CATEGORY %-14s %10.3f ms  %5.1f%%"
                  % (cat, t, 100.0 * t / total if total else 0.0),
                  flush=True)


def analyze():
    # parse the xplane directly (xplane_top_ops): this image's
    # tensorboard_plugin_profile is incompatible with both its protobuf
    # (needs PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION=python) and its TF
    # pywrap (no xspace_to_tools_data) — found pre-staging the hardware
    # run; the direct parser needs neither
    from xplane_top_ops import top_ops

    from paddle_tpu.profiler import device_op_stats, _print_device_op_table

    top_ops(TRACE_DIR)  # globs + asserts the xplane itself
    # Program-op attribution (the executor's pd-scope tags): the
    # reference-style per-op table, conv2d/fused_adam/... level —
    # parse the xplane ONCE and feed both the table and the summary
    table = device_op_stats(TRACE_DIR)
    _print_device_op_table(table)
    _categorize(table)


if __name__ == "__main__":
    from paddle_tpu.core import configure_compile_cache, require_tpu

    require_tpu("tools/bench_profile.py")
    configure_compile_cache()
    model = "bert"
    if "--model" in sys.argv:
        idx = sys.argv.index("--model")
        if idx + 1 >= len(sys.argv):
            raise SystemExit("--model requires a value (bert|resnet)")
        model = sys.argv[idx + 1]
    if model not in ("bert", "resnet"):
        raise SystemExit("unknown --model %r (bert|resnet)" % model)
    if model == "resnet":
        run_and_trace_resnet()
    else:
        run_and_trace()
    analyze()
