"""MFU experiment sweep for the BERT bench step (profiling aid, not CI)."""
import sys
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def run_variant(tag, cfg_kw, batch, seq_len=128, steps=60, warmup=3):
    import paddle_tpu as fluid
    from paddle_tpu.models import bert

    cfg = bert.BertConfig(**cfg_kw)
    main_prog, startup, feed_names, loss = bert.build_pretrain(
        cfg, seq_len=seq_len, lr=1e-4, amp=True, train=True
    )
    from paddle_tpu.executor import Scope, scope_guard

    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        rng = np.random.RandomState(0)
        feed = bert.make_fake_batch(batch, seq_len, cfg, rng)
        for _ in range(warmup):
            exe.run(main_prog, feed=feed, fetch_list=[])
        lv = exe.run(main_prog, feed=feed, fetch_list=[loss])[0]
        t0 = time.perf_counter()
        for _ in range(steps - 1):
            exe.run(main_prog, feed=feed, fetch_list=[])
        lv = exe.run(main_prog, feed=feed, fetch_list=[loss])[0]
        dt = time.perf_counter() - t0
    tps = batch * seq_len * steps / dt
    from bench import model_train_flops_per_token, peak_flops
    import jax

    mfu = tps * model_train_flops_per_token(cfg, seq_len) / peak_flops(
        jax.devices()[0])
    print("%-40s bs=%-4d tokens/sec=%9.0f  MFU=%.3f  loss=%.4f"
          % (tag, batch, tps, mfu, float(np.asarray(lv))), flush=True)


BASE = dict(vocab_size=30522, hidden=768, layers=12, heads=12, ffn=3072,
            max_seq=512)

if __name__ == "__main__":
    from paddle_tpu.core import configure_compile_cache, require_tpu

    require_tpu("tools/bench_sweep.py")
    configure_compile_cache()
    which = sys.argv[1] if len(sys.argv) > 1 else "128"
    if which == "128":
        run_variant("baseline (dropout .1, unfused attn)", dict(BASE), 64)
        run_variant("attn_dropout=0 (flash attn)", dict(BASE, attn_dropout=0.0), 64)
        run_variant("no dropout at all", dict(BASE, dropout=0.0), 64)
        run_variant("baseline bs128", dict(BASE), 128)
        run_variant("attn_dropout=0 bs128", dict(BASE, attn_dropout=0.0), 128)
        run_variant("no dropout bs128", dict(BASE, dropout=0.0), 128)
    elif which == "attn":
        run_variant("attn_dropout=0 (fused attn)", dict(BASE, attn_dropout=0.0), 64)
        run_variant("no dropout at all", dict(BASE, dropout=0.0), 64)
    elif which == "512":
        run_variant("seq512 bs16 dropout .1", dict(BASE), 16, seq_len=512)
        run_variant("seq512 bs16 attn_dropout=0 (flash)", dict(BASE, attn_dropout=0.0), 16, seq_len=512)
        run_variant("seq512 bs32 dropout .1", dict(BASE), 32, seq_len=512)
        run_variant("seq512 bs32 attn_dropout=0 (flash)", dict(BASE, attn_dropout=0.0), 32, seq_len=512)