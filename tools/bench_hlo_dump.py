"""Dump the optimized HLO of a bench train step (layout/fusion
diagnosis).  ``--model bert`` (default) or ``--model resnet50``;
``--summary`` prints op-category counts (the conv/BN-fusion pre-stage
check for the ResNet MFU work)."""
import sys

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def _lower(model):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as fluid
    from paddle_tpu.executor import Scope, scope_guard, _CompiledBlock

    rng = np.random.RandomState(0)
    if model == "resnet50":
        from paddle_tpu.models import resnet

        # fusion structure is batch-independent; small batch keeps the
        # CPU compile tractable (--batch N / --dataset cifar10 to
        # override — the conv/BN lowering is shared, so the cifar net
        # answers the fusion question when the 224² compile is too slow)
        batch = 8
        if "--batch" in sys.argv:
            batch = int(sys.argv[sys.argv.index("--batch") + 1])
        dataset = "imagenet"
        if "--dataset" in sys.argv:
            dataset = sys.argv[sys.argv.index("--dataset") + 1]
        if dataset not in ("imagenet", "cifar10"):
            raise SystemExit("--dataset must be imagenet or cifar10")
        # same branch condition as resnet.build: cifar10 is the small
        # net, everything else is the 224² imagenet net
        size = 32 if dataset == "cifar10" else 224
        nclass = 10 if dataset == "cifar10" else 1000
        main_prog, startup, _, loss, _ = resnet.build(
            dataset=dataset, amp="--no-amp" not in sys.argv)
        feed = {
            "img": rng.randn(batch, 3, size, size).astype("float32"),
            "label": rng.randint(0, nclass, (batch, 1)).astype("int64"),
        }
    else:
        from paddle_tpu.models import bert

        cfg = bert.BERT_BASE
        batch, seq_len = 64, 128
        main_prog, startup, _, loss = bert.build_pretrain(
            cfg, seq_len=seq_len, lr=1e-4, amp=True, train=True
        )
        feed = bert.make_fake_batch(batch, seq_len, cfg, rng)
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        feed_vals = {k: jnp.asarray(v) for k, v in feed.items()}
        cb = _CompiledBlock(main_prog, main_prog.global_block(),
                           list(feed_vals), [], scope, "train")
        rw = {n: scope.get(n) for n in cb.rw_names}
        ro = {n: scope.get(n) for n in cb.ro_names}
        key = jax.random.key(0)
        return cb.jitted.lower(feed_vals, rw, ro, key).compile().as_text()


def summarize(txt):
    """Count the op categories that matter for MXU/HBM efficiency."""
    import re

    cats = {
        "convolution": r"= \S+ convolution\(",
        "dot/matmul": r"= \S+ dot\(",
        "fusion": r"= \S+ fusion\(",
        "batch-norm-unfused": r"batch-norm-(training|inference|grad)",
        "transpose (standalone)": r"^\s*\S+ = \S+ transpose\(",
        "all-reduce": r"all-reduce",
        "copy (layout change)": r"= \S+ copy\(",
        "reduce": r"= \S+ reduce\(",
    }
    counts = {k: len(re.findall(p, txt, re.M)) for k, p in cats.items()}
    # conv/BN fusion health: a fused resnet should show ZERO standalone
    # batch-norm ops (decomposed + fused into neighbors by XLA)
    return counts


def main():
    model = "bert"
    if "--model" in sys.argv:
        model = sys.argv[sys.argv.index("--model") + 1]
    txt = _lower(model)
    path = "/tmp/bench_hlo_%s.txt" % model
    open(path, "w").write(txt)
    print("wrote %s %d bytes" % (path, len(txt)))
    if "--summary" in sys.argv:
        for k, v in summarize(txt).items():
            print("%-26s %6d" % (k, v))


if __name__ == "__main__":
    main()
