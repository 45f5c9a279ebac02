"""XLA cost-model view of a full train step — the offline perf oracle.

Compiles the flagship (bert) or resnet train step through the real
Executor lowering on the CPU backend and prints XLA's own accounting:
FLOPs, bytes accessed, temp/output/alias sizes.  This is how a
flat-stream optimizer fusion (since deleted) was convicted without a chip
(145GB per-parameter vs 664GB concatenated bytes accessed on the BERT-base
bs64 step, matching the hardware MFU drop 0.42->0.30), and how the
framework was shown to be ~2x cheaper than the hand-written pure-jax
control (291GB).

Absolute numbers are CPU-backend artifacts; the value is in A/B deltas
under env knobs (PADDLE_TPU_FUSION, PADDLE_TPU_PALLAS) and model edits.

Usage:  python tools/step_cost.py [bert|resnet] [batch]
        PADDLE_TPU_FUSION=0 python tools/step_cost.py bert 64
"""

import sys

import numpy as np


def main():
    model = sys.argv[1] if len(sys.argv) > 1 else "bert"
    bs = int(sys.argv[2]) if len(sys.argv) > 2 else 64

    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    sys.path.insert(0, __file__.rsplit("/", 2)[0])
    import paddle_tpu as fluid
    import paddle_tpu.executor as ex
    from paddle_tpu.executor import Scope, scope_guard

    rng = np.random.RandomState(0)
    if model == "bert":
        from paddle_tpu.models import bert

        cfg = bert.BERT_BASE
        main_p, startup, feeds, loss = bert.build_pretrain(
            cfg, seq_len=128, lr=1e-4, amp=True, train=True)
        feed = {k: jnp.asarray(v)
                for k, v in bert.make_fake_batch(bs, 128, cfg, rng).items()}
    elif model == "resnet":
        from paddle_tpu.models import resnet

        main_p, startup, feeds, loss, _ = resnet.build(
            dataset="imagenet", amp=True)
        feed = {
            "img": jnp.asarray(rng.randn(bs, 3, 224, 224).astype("float32")),
            "label": jnp.asarray(
                rng.randint(0, 1000, (bs, 1)).astype("int64")),
        }
    else:
        raise SystemExit("unknown model %r (bert|resnet)" % model)

    sc = Scope()
    with scope_guard(sc):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        cb = ex._CompiledBlock(main_p, main_p.global_block(),
                               list(feed.keys()), [loss.name], sc, "train")
        rw = {n: sc.get(n) for n in cb.rw_names}
        ro = {n: sc.get(n) for n in cb.ro_names}
        comp = cb.jitted.lower(feed, rw, ro, ex.rng_key(0)).compile()
    ca = comp.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    mem = comp.memory_analysis()
    flops = ca.get("flops", 0)
    byts = ca.get("bytes accessed", 0)
    print("%s bs%d: flops=%.3fT bytes=%.3fGB temp=%.0fMB out=%.0fMB "
          "alias=%.0fMB ai=%.0f flops/byte"
          % (model, bs, flops / 1e12, byts / 1e9,
             mem.temp_size_in_bytes / 1e6, mem.output_size_in_bytes / 1e6,
             mem.alias_size_in_bytes / 1e6, flops / max(byts, 1)))


if __name__ == "__main__":
    main()
