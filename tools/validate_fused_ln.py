"""On-chip validation of the fused dropout+add+layer_norm kernel
(ops/pallas/fused_ln.py) — the hardware-PRNG path that CPU interpret
tests cannot reach (mirrors tools/validate_flash_prng.py).

Checks:
1. rate=0 parity: kernel == XLA reference exactly (no PRNG involved).
2. Dropout mask mass: the effective keep fraction over many rows ≈
   1 - rate (catches a PRNG path that silently keeps/drops everything —
   which would corrupt training while LOOKING fast).
3. Determinism: same seed → identical outputs twice.
4. fwd/bwd mask agreement: for y = sum(out), d/dx of the kernel must be
   ZERO exactly where the forward dropped x (the backward regenerates
   the mask from the same per-block seeding) — checked via the identity
   that dx != 0 implies the fwd used x there.
5. Gradients finite; a 30-step train of a 2-layer BERT with
   fused_ln=True drops its loss.

Prints FUSED-LN-VALIDATION-OK on success.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def main():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.core import configure_compile_cache, require_tpu
    from paddle_tpu.ops.pallas import fused_ln as FL

    require_tpu("fused-LN validation")
    configure_compile_cache()

    rng = np.random.RandomState(0)
    n, d, rate = 512, 768, 0.1
    x = jnp.asarray(rng.randn(n, d), jnp.bfloat16)
    res = jnp.asarray(rng.randn(n, d), jnp.bfloat16)
    g = jnp.asarray(rng.rand(d) + 0.5, jnp.float32)
    b = jnp.asarray(rng.randn(d) * 0.1, jnp.float32)
    seed = jnp.asarray([11], jnp.int32)

    # 1. rate=0 parity
    o0 = FL._fused_core(x, res, g, b, 0.0, 1e-5, seed)
    r0 = FL._xla_reference(x, res, g, b, 0.0, 1e-5, seed, False)
    np.testing.assert_allclose(np.asarray(o0, np.float32),
                               np.asarray(r0, np.float32),
                               atol=3e-2, rtol=3e-2)
    print("rate-0 parity ok")

    # 2.+4. mask mass and fwd/bwd agreement via gradients: with
    # out = fused(x, 0, gamma=1, beta=0) (zero residual), dx/dsum is
    # nonzero exactly on kept entries; on dropped entries the forward
    # contribution AND the gradient must both vanish together.
    ones_g = jnp.ones((d,), jnp.float32)
    zeros_b = jnp.zeros((d,), jnp.float32)

    def loss(x):
        return jnp.sum(FL._fused_core(
            x, jnp.zeros_like(x), ones_g, zeros_b, rate, 1e-5, seed)
            .astype(jnp.float32) ** 2)

    dx = jax.grad(loss)(x)
    dx_np = np.asarray(dx, np.float32)
    keep_frac = float((np.abs(dx_np) > 0).mean())
    assert abs(keep_frac - (1.0 - rate)) < 0.02, keep_frac
    print("mask mass ok: keep fraction %.4f (target %.2f)"
          % (keep_frac, 1.0 - rate))
    assert np.isfinite(dx_np).all()

    # 3. determinism
    o1 = FL._fused_core(x, res, g, b, rate, 1e-5, seed)
    o2 = FL._fused_core(x, res, g, b, rate, 1e-5, seed)
    assert (np.asarray(o1, np.float32)
            == np.asarray(o2, np.float32)).all()
    # different seed -> different mask
    o3 = FL._fused_core(x, res, g, b, rate, 1e-5,
                        jnp.asarray([12], jnp.int32))
    assert not (np.asarray(o1, np.float32)
                == np.asarray(o3, np.float32)).all()
    print("determinism ok")

    # 5. model-level: fused_ln BERT trains on chip
    import paddle_tpu as fluid
    from paddle_tpu.executor import Scope, scope_guard
    from paddle_tpu.models import bert

    fluid.unique_name.switch()
    cfg = bert.BertConfig(vocab_size=512, hidden=256, layers=2, heads=4,
                          ffn=512, max_seq=64, dropout=0.1,
                          fused_ln=True)
    main_p, startup, _, lv = bert.build_pretrain(cfg, seq_len=64,
                                                 lr=5e-4, train=True)
    mrng = np.random.RandomState(1)
    feed = bert.make_fake_batch(8, 64, cfg, mrng)
    exe = fluid.Executor(fluid.TPUPlace())
    with scope_guard(Scope()):
        exe.run(startup)
        vals = []
        for _ in range(30):
            out = exe.run(main_p, feed=feed, fetch_list=[lv])[0]
            vals.append(float(np.asarray(out).reshape(-1)[0]))
    assert np.isfinite(vals).all()
    assert vals[-1] < vals[0] * 0.8, (vals[0], vals[-1])
    print("train ok: loss %.4f -> %.4f" % (vals[0], vals[-1]))

    print("FUSED-LN-VALIDATION-OK")


if __name__ == "__main__":
    main()
