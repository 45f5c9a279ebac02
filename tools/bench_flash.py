"""Measure the Pallas flash-attention kernel against the XLA attention
path on the real chip: fwd+bwd wall time and effective MFU at the shapes
that matter (T=128 — the deferral boundary — and T=512/1024/2048, with
and without in-kernel dropout).

Decides VERDICT r2 #3: is the T<256 deferral justified, and does the
kernel hit >= 0.40 attention-MFU at seq512 with dropout on?

Usage (on TPU):  python tools/bench_flash.py [--csv]
"""

import argparse
import math
import sys
import time

import numpy as np


def bench_case(T, dropout, use_kernel, B=16, H=12, D=64, steps=30,
               block_q=None, block_k=None, DV=None, causal=False):
    """use_kernel: False = XLA fallback, True = our Pallas kernel,
    "jax" = the upstream jax.experimental TPU flash kernel (no-dropout
    comparator: how far is our kernel from the stock tuned one?)."""
    import os

    jax_impl = use_kernel == "jax"
    os.environ["PADDLE_TPU_PALLAS"] = (
        "auto" if use_kernel and not jax_impl else "off")
    # force the kernel at EVERY T (the tool exists to re-decide the
    # default T<256 deferral, so the boundary must not gate the sweep)
    os.environ["PADDLE_TPU_FLASH_MIN_T"] = (
        "1" if use_kernel and not jax_impl else "256")
    for var, val in (("PADDLE_TPU_FLASH_BLOCK_Q", block_q),
                     ("PADDLE_TPU_FLASH_BLOCK_K", block_k)):
        if val is None:
            os.environ.pop(var, None)
        else:
            os.environ[var] = str(val)

    import jax
    import jax.numpy as jnp

    # the package __init__ once re-exported the flash_attention
    # FUNCTION under this name, shadowing the submodule and breaking
    # every kernel arm of a hardware sweep ('function' object has no
    # attribute) — see ops/pallas/__init__.py for the standing rule
    import paddle_tpu.ops.pallas.flash_attention as FA

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32),
                    dtype=jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32),
                    dtype=jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, H, T, DV or D).astype(np.float32),
                    dtype=jnp.bfloat16)
    seed = jnp.asarray([3], jnp.int32)

    if jax_impl:
        from jax.experimental.pallas.ops.tpu import (
            flash_attention as UFA,
        )

        def loss(q, k, v):
            o = UFA.flash_attention(q, k, v,
                                    sm_scale=1.0 / math.sqrt(D))
            return jnp.sum(o.astype(jnp.float32) ** 2)
    else:
        def loss(q, k, v):
            o = FA.flash_attention(
                q, k, v, causal=causal, dropout_rate=dropout,
                dropout_seed=(seed if dropout else None))
            return jnp.sum(o.astype(jnp.float32) ** 2)

    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    l, g = step(q, k, v)   # compile
    jax.block_until_ready((l, g))
    t0 = time.perf_counter()
    for _ in range(steps):
        l, g = step(q, k, v)
    jax.block_until_ready((l, g))
    dt = (time.perf_counter() - t0) / steps
    # attention fwd+bwd FLOPs: fwd 2*B*H*T^2*(D + DV) (scores + PV), bwd
    # ~2.5x; causal needs half of them
    flops = 3.5 * 2 * B * H * T * T * (D + (DV or D)) / (2 if causal else 1)
    mfu = flops / dt / 197e12
    return dt * 1e3, mfu


def block_sweep():
    """Block-shape sweep at the kernel's own regime (VERDICT r4 #4):
    (block_q, block_k) combos at T=512/1024 with dropout on and at the
    causal 192/128 site of T=4096, kernel path only.  Prints per-T
    winners and BLOCK-DECISION lines the watcher artifact records
    (parsed by tools/decide_flash_min_t.py)."""
    best = {}
    # BERT's heads at T 512 / 1024 with dropout; latent attention's site
    # of the kanana cell (4 x 32 heads, 192 / 128 wide, causal) at 4096
    latent = dict(B=4, H=32, D=192, DV=128, causal=True, steps=10)
    for T, dropout, site in ((512, 0.1, {}), (1024, 0.1, {}),
                             (4096, 0.0, latent)):
        for bq in (128, 256, 512, 1024):
            for bk in (128, 256, 512, 1024):
                if bq > T or bk > T:
                    continue
                try:
                    ms, mfu = bench_case(T, dropout, True, block_q=bq,
                                         block_k=bk, **site)
                except Exception as e:  # noqa: BLE001
                    print("# T=%d bq=%d bk=%d FAILED: %s"
                          % (T, bq, bk, str(e)[-160:]), flush=True)
                    continue
                print("T=%-5d bq=%-4d bk=%-4d  %7.3f ms  attn-MFU %.3f"
                      % (T, bq, bk, ms, mfu), flush=True)
                if T not in best or mfu > best[T][2]:
                    best[T] = (bq, bk, mfu)
    for T, (bq, bk, mfu) in sorted(best.items()):
        print("BLOCK-DECISION T=%d: block_q=%d block_k=%d (attn-MFU "
              "%.3f)" % (T, bq, bk, mfu), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--csv", action="store_true")
    ap.add_argument("--blocks", action="store_true",
                    help="sweep kernel block shapes at T=512/1024")
    args = ap.parse_args()

    from paddle_tpu.core import configure_compile_cache, require_tpu

    require_tpu("tools/bench_flash.py")
    configure_compile_cache()

    if args.blocks:
        block_sweep()
        return

    rows = []
    for T in (128, 256, 512, 1024, 2048):
        for dropout in (0.0, 0.1):
            # "jax" = upstream stock kernel, dropout-free only — the
            # is-our-kernel-near-SOTA comparator
            impls = (False, True) if dropout else (False, True, "jax")
            for use_kernel in impls:
                try:
                    ms, mfu = bench_case(T, dropout, use_kernel)
                except Exception as e:  # noqa: BLE001
                    print("# T=%d drop=%.1f kernel=%s FAILED: %s"
                          % (T, dropout, use_kernel, e), flush=True)
                    continue
                rows.append((T, dropout, use_kernel, ms, mfu))
                print("T=%-5d drop=%.1f %-8s  %7.3f ms  attn-MFU %.3f"
                      % (T, dropout,
                         {False: "xla", True: "pallas",
                          "jax": "jaxflash"}[use_kernel], ms, mfu),
                      flush=True)
    if args.csv:
        print("T,dropout,kernel,ms,mfu")
        for r in rows:
            impl = {False: "xla", True: "pallas", "jax": "jaxflash"}[r[2]]
            print("%d,%.2f,%s,%.4f,%.4f"
                  % (r[0], r[1], impl, r[3], r[4]))


if __name__ == "__main__":
    sys.path.insert(0, __file__.rsplit("/", 2)[0])
    main()
