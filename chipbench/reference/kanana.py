"""The kanana-2 decoder's forward in plain ``jax.numpy``, float32: the plain
reference of the ``kanana_2_30b_a3b`` configuration.

Written from DeepSeek-V3's equations, which the source config's
``model_type`` names (arXiv:2412.19437, section 2.1), and sharing no code
with the program:

* block: ``h = x + MLA(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``, a final
  RMSNorm, an untied head;
* MLA: ``q = W_q x`` as heads of ``[q_nope | q_rope]``; ``[c | k_rope] =
  W_kva x``, one ``k_rope`` for all heads; ``[k_nope | v]`` a head ``=
  W_kvb RMSNorm(c)``; rotary on interleaved pairs of ``q_rope`` and
  ``k_rope``; causal ``softmax(q k / sqrt(d_qk)) v``; ``W_o``;
* expert layer: ``s = sigmoid(W_r x)`` over all experts; the top k of ``s
  + b`` chosen; ``g_i = scale * s_i / sum_chosen s_j``; ``sum_chosen g_i
  E_i(x) + S(x)``; ``E`` and ``S`` SwiGLU MLPs.

It is given the same share of the deployment as the program: the experts
``first .. first + held - 1`` (``held`` read from the weights' names) and
the rows of the vocabulary held.  A chosen expert that is not held adds
nothing, here as there.  Attention is computed a block of queries at a
time so that a 4096-token sequence fits beside a training state.

It takes the program's weights by the names ``models/decoder.py`` gives
them and a feed in its layout (``input_ids``, ``labels``: [B, T]).
"""

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _rotary(x, theta):
    """x: [B, T, H, R], pairs (2i, 2i+1) rotated by t * theta^(-2i/R)."""
    t, r = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _causal_attention(q, k, v):
    """q, k: [B, T, H, Dqk]; v: [B, T, H, Dv]; a block of queries a time."""
    t = q.shape[1]
    scale = 1.0 / math.sqrt(q.shape[-1])
    cols = jnp.arange(t)
    out = []
    for lo in range(0, t, QUERY_BLOCK):
        qb = q[:, lo:lo + QUERY_BLOCK]
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * scale
        rows = lo + jnp.arange(qb.shape[1])
        s = jnp.where(rows[:, None] >= cols[None, :], s, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhd->bqhd",
                              jax.nn.softmax(s, axis=-1), v))
    return jnp.concatenate(out, axis=1)


def mla(x, w, p, config):
    b, t, _ = x.shape
    h = config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv, rank = config["v_head_dim"], config["kv_lora_rank"]
    q = (x @ w[p + ".q.w"]).reshape(b, t, h, nope + rope)
    kva = x @ w[p + ".kva.w"]
    c = _rms_norm(kva[..., :rank], w[p + ".kv_norm.scale"],
                  config["rms_norm_eps"])
    k_rope = _rotary(kva[..., rank:].reshape(b, t, 1, rope),
                     config["rope_theta"])
    kv = (c @ w[p + ".kvb.w"]).reshape(b, t, h, nope + dv)
    q = jnp.concatenate([q[..., :nope],
                         _rotary(q[..., nope:], config["rope_theta"])], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (b, t, h, rope))], -1)
    ctx = _causal_attention(q, k, kv[..., nope:])
    return ctx.reshape(b, t, h * dv) @ w[p + ".o.w"]


def _swiglu_mlp(x, w, p):
    return (jax.nn.silu(x @ w[p + ".gate.w"]) * (x @ w[p + ".up.w"])) \
        @ w[p + ".down.w"]


def route(x, w, p, config):
    """Chosen experts [.., k] and their gates, over all experts."""
    s = jax.nn.sigmoid(x @ w[p + ".router.w"])
    _, idx = jax.lax.top_k(s + w[p + ".router.b"],
                           config["num_experts_per_tok"])
    g = jnp.take_along_axis(s, idx, axis=-1)
    if config["norm_topk_prob"]:
        g = g / g.sum(-1, keepdims=True)
    return idx, g * config["routed_scaling_factor"]


def routed_part(x, w, p, config, first):
    """What the held experts (``w[p + '.experts.<e>.*']``, e counted
    from 0 for the expert ``first``) give: every held expert over every
    token, times the gate the token gave it (zero where it did not choose
    it)."""
    idx, g = route(x, w, p, config)
    out = jnp.zeros_like(x)
    e = 0
    while "%s.experts.%d.gate" % (p, e) in w:
        q = "%s.experts.%d" % (p, e)
        y = (jax.nn.silu(x @ w[q + ".gate"]) * (x @ w[q + ".up"])) \
            @ w[q + ".down"]
        out = out + jnp.sum(jnp.where(idx == first + e, g, 0.0),
                            axis=-1)[..., None] * y
        e += 1
    return out


def expert_layer(x, w, p, config, first):
    return routed_part(x, w, p, config, first) \
        + _swiglu_mlp(x, w, p + ".shared")


def first_expert(config):
    return config.get("deployment", {}).get("first_expert", 0)


def hidden(w, ids, config):
    x = w["decoder.embed"][ids]
    eps = config["rms_norm_eps"]
    for i in range(config["num_hidden_layers"]):
        p = "decoder.layer%d" % i
        h = x + mla(_rms_norm(x, w[p + ".ln1.scale"], eps), w,
                    p + ".attn", config)
        n = _rms_norm(h, w[p + ".ln2.scale"], eps)
        if i < config["first_k_dense_replace"]:
            x = h + _swiglu_mlp(n, w, p + ".mlp")
        else:
            x = h + expert_layer(n, w, p + ".moe", config,
                                 first_expert(config))
    return _rms_norm(x, w["decoder.final_norm.scale"], eps)


def forward(w, feed, config):
    """``{"logits": [B, T, vocabulary held], "loss": scalar}``: the mean
    next-token cross-entropy over every position.  Where ``w`` holds what
    the program's first expert layer gave its router (``<layer>.router.x``:
    [N, D]), also ``"route_gates"``, [N, k]: this router's gates for those
    very rows."""
    logits = hidden(w, feed["input_ids"], config) @ w["decoder.head.w"]
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                               feed["labels"][..., None], axis=-1)
    out = {"logits": logits, "loss": nll.mean()}
    p = "decoder.layer%d.moe" % config["first_k_dense_replace"]
    if p + ".router.x" in w:
        out["route_gates"] = route(w[p + ".router.x"], w, p, config)[1]
    return out


def loss(w, feed, config):
    return forward(w, feed, config)["loss"]
