"""Control experiment: hand-written pure-jax BERT-base MLM train step at the
bench config — measures the XLA-on-v5e ceiling independent of the framework
(same math: bf16 compute, f32 master weights + Adam, dropout 0.1)."""
import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

L, D, H, FF, V, T, B = 12, 768, 12, 3072, 30522, 128, 64
DH = D // H


def init_params(key):
    ks = jax.random.split(key, 8)
    p = {
        "wemb": jax.random.normal(ks[0], (V, D), jnp.float32) * 0.02,
        "pemb": jax.random.normal(ks[1], (512, D), jnp.float32) * 0.02,
        "temb": jax.random.normal(ks[2], (2, D), jnp.float32) * 0.02,
        "eln_s": jnp.ones((D,)), "eln_b": jnp.zeros((D,)),
    }
    for i in range(L):
        kk = jax.random.split(ks[3 + (i % 5)], 8)
        p["l%d" % i] = {
            "q": jax.random.normal(kk[0], (D, D)) * 0.02,
            "k": jax.random.normal(kk[1], (D, D)) * 0.02,
            "v": jax.random.normal(kk[2], (D, D)) * 0.02,
            "o": jax.random.normal(kk[3], (D, D)) * 0.02,
            "qb": jnp.zeros((D,)), "kb": jnp.zeros((D,)),
            "vb": jnp.zeros((D,)), "ob": jnp.zeros((D,)),
            "f1": jax.random.normal(kk[4], (D, FF)) * 0.02,
            "f1b": jnp.zeros((FF,)),
            "f2": jax.random.normal(kk[5], (FF, D)) * 0.02,
            "f2b": jnp.zeros((D,)),
            "ln1s": jnp.ones((D,)), "ln1b": jnp.zeros((D,)),
            "ln2s": jnp.ones((D,)), "ln2b": jnp.zeros((D,)),
        }
    return p


def ln(x, s, b):
    x32 = x.astype(jnp.float32)
    m = x32.mean(-1, keepdims=True)
    v = ((x32 - m) ** 2).mean(-1, keepdims=True)
    return ((x32 - m) * jax.lax.rsqrt(v + 1e-5)).astype(x.dtype) * s.astype(
        x.dtype) + b.astype(x.dtype)


def dropout(key, x, rate=0.1):
    keep = jax.random.bernoulli(key, 1 - rate, x.shape)
    return jnp.where(keep, x / (1 - rate), 0).astype(x.dtype)


def fwd(p, batch, key):
    ids, types, pos, bias = batch["ids"], batch["types"], batch["pos"], batch["bias"]
    x = (p["wemb"][ids] + p["pemb"][pos] + p["temb"][types])
    x = ln(x, p["eln_s"], p["eln_b"]).astype(jnp.bfloat16)
    keys = jax.random.split(key, 3 * L + 1)
    x = dropout(keys[-1], x)
    scale = 1.0 / np.sqrt(DH)
    for i in range(L):
        lp = jax.tree.map(lambda a: a.astype(jnp.bfloat16), p["l%d" % i])
        q = (x @ lp["q"] + lp["qb"]).reshape(B, T, H, DH).transpose(0, 2, 1, 3)
        k = (x @ lp["k"] + lp["kb"]).reshape(B, T, H, DH).transpose(0, 2, 1, 3)
        v = (x @ lp["v"] + lp["vb"]).reshape(B, T, H, DH).transpose(0, 2, 1, 3)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale + bias.astype(jnp.bfloat16)
        pr = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(jnp.bfloat16)
        pr = dropout(keys[3 * i], pr)
        ctx = jnp.einsum("bhqk,bhkd->bhqd", pr, v).transpose(0, 2, 1, 3).reshape(B, T, D)
        attn = ctx @ lp["o"] + lp["ob"]
        attn = dropout(keys[3 * i + 1], attn)
        x = ln(x + attn, lp["ln1s"], lp["ln1b"])
        ff = jax.nn.gelu((x @ lp["f1"] + lp["f1b"]).astype(jnp.float32)).astype(jnp.bfloat16)
        ff = ff @ lp["f2"] + lp["f2b"]
        ff = dropout(keys[3 * i + 2], ff)
        x = ln(x + ff, lp["ln2s"], lp["ln2b"])
    logits = x @ p["wemb"].astype(jnp.bfloat16).T
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, batch["labels"][..., None], axis=-1)[..., 0]
    w = batch["weights"]
    return -(ll * w).sum() / w.sum()


def adam_update(p, g, m1, m2, step, lr=1e-4, b1=0.9, b2=0.999, eps=1e-8):
    m1 = b1 * m1 + (1 - b1) * g
    m2 = b2 * m2 + (1 - b2) * g * g
    lr_t = lr * jnp.sqrt(1 - b2 ** step) / (1 - b1 ** step)
    return p - lr_t * m1 / (jnp.sqrt(m2) + eps), m1, m2


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def train_step(p, m1, m2, step, batch, key):
    loss, grads = jax.value_and_grad(fwd)(p, batch, key)
    new = jax.tree.map(
        lambda pp, gg, a, b: adam_update(pp, gg, a, b, step),
        p, grads, m1, m2,
        is_leaf=lambda x: isinstance(x, jnp.ndarray),
    )
    np_ = jax.tree.map(lambda t: t[0], new, is_leaf=lambda x: isinstance(x, tuple))
    nm1 = jax.tree.map(lambda t: t[1], new, is_leaf=lambda x: isinstance(x, tuple))
    nm2 = jax.tree.map(lambda t: t[2], new, is_leaf=lambda x: isinstance(x, tuple))
    return np_, nm1, nm2, loss


def main():
    from paddle_tpu.core import configure_compile_cache, require_tpu

    require_tpu("tools/bench_pure_jax.py")
    configure_compile_cache()
    rng = np.random.RandomState(0)
    p = init_params(jax.random.key(0))
    m1 = jax.tree.map(jnp.zeros_like, p)
    m2 = jax.tree.map(jnp.zeros_like, p)
    batch = {
        "ids": jnp.asarray(rng.randint(10, V, (B, T)), jnp.int32),
        "types": jnp.zeros((B, T), jnp.int32),
        "pos": jnp.tile(jnp.arange(T, dtype=jnp.int32), (B, 1)),
        "bias": jnp.zeros((B, 1, 1, T), jnp.float32),
        "labels": jnp.asarray(rng.randint(10, V, (B, T)), jnp.int32),
        "weights": jnp.asarray(rng.rand(B, T) < 0.15, jnp.float32),
    }
    key = jax.random.key(1)
    steps = 20
    for i in range(3):
        p, m1, m2, loss = train_step(p, m1, m2, jnp.float32(i + 1), batch,
                                     jax.random.fold_in(key, i))
    # sync with a data fetch (same protocol as bench.py's final
    # fetch_list=[loss])
    float(loss)
    t0 = time.perf_counter()
    for i in range(steps):
        p, m1, m2, loss = train_step(p, m1, m2, jnp.float32(i + 4), batch,
                                     jax.random.fold_in(key, 100 + i))
    lv = float(loss)  # forces the whole donated-param chain
    dt = time.perf_counter() - t0
    tps = B * T * steps / dt
    from bench import model_train_flops_per_token, peak_flops

    class Cfg:
        hidden, ffn, layers, vocab_size = D, FF, L, V

    # max_pred=0: this control scores ALL positions in its MLM head, so
    # its MFU denominator must count the full vocab projection (the
    # framework model gathers masked positions and uses the default)
    mfu = (tps * model_train_flops_per_token(Cfg, T, max_pred=0)
           / peak_flops(jax.devices()[0]))
    print("pure-jax: tokens/sec=%.0f MFU=%.3f loss=%.4f"
          % (tps, mfu, lv))


if __name__ == "__main__":
    main()
