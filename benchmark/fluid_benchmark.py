"""The reference benchmark harness CLI, TPU-native (reference:
``benchmark/fluid/fluid_benchmark.py`` + ``args.py`` + ``models/*`` —
same flags, same workloads, same ``%.5f examples/sed`` reporting after
timed passes, reference line 296-300, typo included).

    python benchmark/fluid_benchmark.py --model mnist --device CPU
    python benchmark/fluid_benchmark.py --model resnet --batch_size 64 \
        --iterations 60                       # TPU, bf16 AMP
    python benchmark/fluid_benchmark.py --model vgg --update_method \
        collective                            # GSPMD data parallel

The reference's ``--update_method pserver|nccl2`` cluster modes are
subsumed: ``collective`` jits the same program over every visible
device (GSPMD inserts the ICI collectives); multi-host runs come from
``jax.distributed`` + the fleet role env vars, not from relaunching
this script per role.
"""

import argparse
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

MODELS = ("mnist", "resnet", "vgg", "stacked_dynamic_lstm",
          "machine_translation", "se_resnext")


def parse_args():
    ap = argparse.ArgumentParser("fluid_benchmark")
    ap.add_argument("--model", choices=MODELS, default="resnet")
    ap.add_argument("--batch_size", type=int, default=32)
    ap.add_argument("--learning_rate", type=float, default=1e-3)
    ap.add_argument("--pass_num", type=int, default=1)
    ap.add_argument("--iterations", type=int, default=30,
                    help="steps per pass")
    ap.add_argument("--device", choices=("CPU", "TPU"), default="TPU")
    ap.add_argument("--update_method", choices=("local", "collective"),
                    default="local",
                    help="collective = GSPMD data parallel over all "
                         "visible devices")
    ap.add_argument("--profile", action="store_true",
                    help="profile one pass (per-op device table)")
    ap.add_argument("--no_amp", action="store_true",
                    help="disable bf16 AMP where the model supports it")
    ap.add_argument("--data_format", choices=("NCHW", "NHWC"),
                    default="NCHW",
                    help="conv layout (reference args.py:50; unlike the "
                         "reference, NHWC is fully supported — it is the "
                         "TPU-native layout; wired for resnet and vgg)")
    return ap.parse_args()


def build_model(args, on_tpu):
    """Returns (main, startup, feed_fn, loss) — feed_fn(batch_size) makes
    one feed dict (synthetic data; the harness measures the framework,
    reference models/__init__ does the same for several workloads)."""
    from paddle_tpu import models

    rng = np.random.RandomState(0)
    m = args.model
    if m == "mnist":
        main, startup, feeds, loss, acc = models.mnist.build(
            lr=args.learning_rate)

        def feed_fn(bs):
            return {"img": rng.rand(bs, 784).astype("float32"),
                    "label": rng.randint(0, 10, (bs, 1)).astype("int64")}
    elif m == "resnet":
        dataset = "imagenet" if on_tpu else "cifar10"
        main, startup, feeds, loss, acc = models.resnet.build(
            dataset=dataset, amp=on_tpu and not args.no_amp,
            data_format=getattr(args, "data_format", "NCHW"))
        # single source of truth: the builder's declared img shape
        # (feeds[0].shape is [-1, ...]) — no third copy of the
        # layout/size conditional
        img_shape = tuple(feeds[0].shape[1:])

        def feed_fn(bs):
            return {"img": rng.randn(bs, *img_shape).astype("float32"),
                    "label": rng.randint(0, 10, (bs, 1)).astype("int64")}
    elif m == "vgg":
        main, startup, feeds, loss, acc = models.vgg.build(
            dataset="cifar10", lr=args.learning_rate,
            data_format=getattr(args, "data_format", "NCHW"))
        img_shape = tuple(feeds[0].shape[1:])

        def feed_fn(bs):
            return {"img": rng.randn(bs, *img_shape).astype("float32"),
                    "label": rng.randint(0, 10, (bs, 1)).astype("int64")}
    elif m == "stacked_dynamic_lstm":
        seq_len, vocab = 80, 5149
        main, startup, feeds, loss, acc = models.stacked_dynamic_lstm.build(
            vocab_size=vocab, seq_len=seq_len, emb_dim=64, hidden_dim=64,
            lr=args.learning_rate)

        def feed_fn(bs):
            lens = rng.randint(8, seq_len + 1, (bs,))
            return {
                "words": rng.randint(0, vocab, (bs, seq_len)).astype(
                    "int64"),
                "lens": lens.astype("int64"),
                "label": rng.randint(0, 2, (bs, 1)).astype("int64"),
            }
    elif m == "machine_translation":
        vocab, src_len, tgt_len = 10000, 16, 16
        main, startup, feeds, loss = models.machine_translation.build_train(
            vocab, src_len=src_len, tgt_len=tgt_len,
            lr=args.learning_rate)

        def feed_fn(bs):
            return {
                "src": rng.randint(0, vocab, (bs, src_len)).astype(
                    "int64"),
                "tgt_in": rng.randint(0, vocab, (bs, tgt_len)).astype(
                    "int64"),
                "tgt_out": rng.randint(
                    0, vocab, (bs, tgt_len, 1)).astype("int64"),
            }
    else:  # se_resnext
        main, startup, feeds, loss, acc = models.se_resnext.build(
            lr=args.learning_rate)

        def feed_fn(bs):
            return {"img": rng.randn(bs, 3, 32, 32).astype("float32"),
                    "label": rng.randint(0, 10, (bs, 1)).astype("int64")}
    return main, startup, feed_fn, loss


def main():
    args = parse_args()
    if args.data_format != "NCHW" and args.model not in ("resnet", "vgg"):
        raise SystemExit(
            "--data_format NHWC is only wired for resnet and vgg; "
            "refusing to record a run under a layout it would not use")
    import jax

    if args.device == "CPU":
        jax.config.update("jax_platforms", "cpu")

    import paddle_tpu as fluid
    from paddle_tpu import profiler
    from paddle_tpu.executor import Scope, scope_guard

    # a missing chip is JAX's own start-up error; a backend other than
    # the one asked for is ours — a run is never recorded under a device
    # it did not use
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if args.device == "TPU" and not on_tpu:
        raise SystemExit(
            "--device TPU but JAX reports platform %r; pass --device CPU "
            "to run on the CPU" % dev.platform)
    main_prog, startup, feed_fn, loss = build_model(args, on_tpu)

    run_prog = main_prog
    if args.update_method == "collective":
        if args.batch_size % len(jax.devices()):
            raise SystemExit(
                "--batch_size must divide the %d devices for collective "
                "mode" % len(jax.devices()))
        run_prog = fluid.CompiledProgram(main_prog).with_data_parallel(
            loss_name=loss.name)

    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        feed = feed_fn(args.batch_size)
        # warmup/compile outside the timed window (reference skips the
        # first iterations the same way)
        exe.run(run_prog, feed=feed, fetch_list=[loss])
        total_examples = 0
        total_time = 0.0
        for pass_id in range(args.pass_num):
            if args.profile and pass_id == 0:
                profiler.start_profiler("All")
            t0 = time.perf_counter()
            for _ in range(args.iterations - 1):
                exe.run(run_prog, feed=feed, fetch_list=[])
            lv = exe.run(run_prog, feed=feed, fetch_list=[loss])[0]
            dt = time.perf_counter() - t0
            if args.profile and pass_id == 0:
                profiler.stop_profiler("total", "/tmp/fluid_bench_profile")
            n = args.batch_size * args.iterations
            total_examples += n
            total_time += dt
            print("Pass: %d, Loss: %f, Speed: %.5f examples/sed"
                  % (pass_id, float(np.asarray(lv).reshape(-1)[0]),
                     n / dt), flush=True)
        print("Total examples: %d, Total time: %.2fs, %.5f examples/sed"
              % (total_examples, total_time,
                 total_examples / total_time), flush=True)


if __name__ == "__main__":
    main()
