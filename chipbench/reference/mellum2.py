"""The Mellum 2 decoder's forward in plain ``jax.numpy``, float32: the plain
reference of the ``mellum2_12b_a2_5b`` configuration.

Written from the equations of ISSUE 36 (the source config's keys,
``model_type: mellum``: grouped-query attention, three sliding layers to a
full one, a softmax top-k router), and sharing no code with the program:

* block: ``h = x + Attn_l(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``, a
  final RMSNorm, an untied head;
* attention: ``q = W_q n`` as ``num_attention_heads`` heads of
  ``head_dim``, ``k = W_k n`` and ``v = W_v n`` as ``num_key_value_heads``;
  rotary on the whole head, pairs ``(i, i + head_dim / 2)``, angle ``p *
  f_i``.  A sliding layer: ``f_i = theta^(-2i/head_dim)``.  A full layer
  (YaRN, static): ``c(r) = head_dim ln(original / (2 pi r)) / (2 ln
  theta)``, ``low = max(floor(c(beta_fast)), 0)``, ``high =
  min(ceil(c(beta_slow)), head_dim / 2 - 1)``, ``ramp_i = clip((i - low) /
  (high - low), 0, 1)``, ``f'_i = (1 - ramp_i) f_i + ramp_i f_i / factor``,
  cos and sin both times ``attention_factor``.  Query head h reads
  key-value head ``h // group``; scores ``q.k / sqrt(head_dim)``; key j is
  visible to query i iff ``j <= i`` and, in a sliding layer, ``i - j <
  sliding_window``; softmax; ``W_o``;
* expert layer: ``p = softmax(n W_r)`` over all the experts; the top k of
  ``log p + b`` chosen (``b`` the bias the last training step left; the
  published model has none, and with ``b = 0`` these are the k largest
  ``p``); gates ``p_e / sum_chosen p``; ``sum_chosen gate_e
  E_e(n)``, ``E`` a SwiGLU MLP.  No shared expert.

It is given the same share of the deployment as the program: the experts
``first .. first + held - 1`` (``held`` read from the weights' names) and
the rows of the vocabulary held.  A chosen expert that is not held adds
nothing, here as there.  Attention is computed a head and a block of
queries at a time, so that 8192 x 8192 scores never exist at once.

It takes the program's weights by the names ``models/decoder.py`` gives
them and a feed in its layout (``input_ids``, ``labels``: [B, T]).
"""

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def frequencies(rope, head_dim):
    """``(f [head_dim / 2], the factor on cos and sin)`` of one section
    of ``rope_parameters``."""
    pairs = head_dim // 2
    i = jnp.arange(pairs, dtype=jnp.float32)
    f = rope["rope_theta"] ** (-2.0 * i / head_dim)
    if rope["rope_type"] == "default":
        return f, 1.0

    def c(r):
        return (head_dim * math.log(rope["original_max_position_embeddings"]
                                    / (2 * math.pi * r))
                / (2 * math.log(rope["rope_theta"])))

    low = max(math.floor(c(rope["beta_fast"])), 0)
    high = min(math.ceil(c(rope["beta_slow"])), pairs - 1)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return ((1.0 - ramp) * f + ramp * f / rope["factor"],
            rope["attention_factor"])


def _rotary(x, rope):
    """x: [B, T, H, D], pair i = features (i, i + D/2)."""
    t, d = x.shape[1], x.shape[-1]
    f, factor = frequencies(rope, d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * f[None, :]
    cos = (jnp.cos(ang) * factor)[None, :, None, :]
    sin = (jnp.sin(ang) * factor)[None, :, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attend(q, k, v, window):
    """q: [B, T, H, D]; k, v: [B, T, Hkv, D]; a head (``lax.map``) and a
    block of queries at a time.  ``window`` None: every key up to the
    query's."""
    t, heads = q.shape[1], q.shape[2]
    group = heads // k.shape[2]
    scale = 1.0 / math.sqrt(q.shape[-1])
    cols = jnp.arange(t)

    def one_head(h):
        qh = jax.lax.dynamic_index_in_dim(q, h, 2, keepdims=False)
        kh = jax.lax.dynamic_index_in_dim(k, h // group, 2, keepdims=False)
        vh = jax.lax.dynamic_index_in_dim(v, h // group, 2, keepdims=False)
        out = []
        for lo in range(0, t, QUERY_BLOCK):
            qb = qh[:, lo:lo + QUERY_BLOCK]
            s = jnp.einsum("bqd,bkd->bqk", qb, kh) * scale
            ahead = (lo + jnp.arange(qb.shape[1]))[:, None] - cols[None, :]
            seen = ahead >= 0
            if window is not None:
                seen &= ahead < window
            s = jnp.where(seen, s, -jnp.inf)
            out.append(jnp.einsum("bqk,bkd->bqd",
                                  jax.nn.softmax(s, axis=-1), vh))
        return jnp.concatenate(out, axis=1)

    return jnp.moveaxis(jax.lax.map(one_head, jnp.arange(heads)), 0, 2)


def attention_heads(x, w, p, config, kind):
    """The attention output before ``W_o``: [B, T, heads * head_dim]."""
    b, t, _ = x.shape
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    d, rope = config["head_dim"], config["rope_parameters"][kind]
    q = _rotary((x @ w[p + ".q.w"]).reshape(b, t, heads, d), rope)
    k = _rotary((x @ w[p + ".k.w"]).reshape(b, t, kv, d), rope)
    v = (x @ w[p + ".v.w"]).reshape(b, t, kv, d)
    window = config["sliding_window"] if kind == "sliding_attention" \
        else None
    return _attend(q, k, v, window).reshape(b, t, heads * d)


def route(x, w, p, config):
    """Chosen experts [.., k] and their gates, over all experts."""
    z = x @ w[p + ".router.w"]
    s = jax.nn.softmax(z, axis=-1)
    _, idx = jax.lax.top_k(jax.nn.log_softmax(z, axis=-1)
                           + w[p + ".router.b"],
                           config["num_experts_per_tok"])
    g = jnp.take_along_axis(s, idx, axis=-1)
    if config["norm_topk_prob"]:
        g = g / g.sum(-1, keepdims=True)
    return idx, g


def expert_layer(x, w, p, config, first):
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


def first_expert(config):
    return config.get("deployment", {}).get("first_expert", 0)


def forward(w, feed, config):
    """``{"logits": [B, T, vocabulary held], "loss": the mean next-token
    cross-entropy over every position, "attn_sliding", "attn_full": the
    attention output before W_o of the first sliding and the first full
    layer}``.  Where ``w`` holds what the program's first expert layer
    gave its router (``<layer>.router.x``: [N, D]), also
    ``"route_gates"``, [N, k]: this router's gates for those very rows."""
    x = w["decoder.embed"][feed["input_ids"]]
    eps, kinds = config["rms_norm_eps"], config["layer_types"]
    out = {}
    for i in range(config["num_hidden_layers"]):
        p = "decoder.layer%d" % i
        heads = attention_heads(_rms_norm(x, w[p + ".ln1.scale"], eps), w,
                                p + ".attn", config, kinds[i])
        out.setdefault("attn_sliding" if kinds[i] == "sliding_attention"
                       else "attn_full", heads)
        h = x + heads @ w[p + ".attn.o.w"]
        x = h + expert_layer(_rms_norm(h, w[p + ".ln2.scale"], eps), w,
                             p + ".moe", config, first_expert(config))
    logits = _rms_norm(x, w["decoder.final_norm.scale"], eps) \
        @ w["decoder.head.w"]
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                               feed["labels"][..., None], axis=-1)
    out.update(logits=logits, loss=nll.mean())
    p = "decoder.layer0.moe"
    if p + ".router.x" in w:
        out["route_gates"] = route(w[p + ".router.x"], w, p, config)[1]
    return out


def loss(w, feed, config):
    return forward(w, feed, config)["loss"]
