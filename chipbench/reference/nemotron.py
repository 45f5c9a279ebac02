"""The Nemotron 3 Nano decoder's forward in plain ``jax.numpy``, float32: the
plain reference of the ``nemotron_3_nano_30b_a3b`` configuration.

Written from the equations of the source config's family (``model_type:
nemotron_h``, HF's ``NemotronH``: ``NemotronHBlock``, ``NemotronHMamba2Mixer``,
``MambaRMSNormGated``, ``NemotronHMOE``, ``NemotronHAttention``), and sharing
no code with the program:

* ``h0 = E[ids]``; layer i is ONE part, by ``hybrid_override_pattern[i]``:
  ``x = x + part(RMSNorm(x))``; a final RMSNorm and an untied head;
* ``M``, a Mamba-2 mixer: ``[z | xBC | dt] = W_in u`` (widths ``H P`` with
  ``H = mamba_num_heads``, ``P = mamba_head_dim``; ``H P + 2 G N`` with ``G
  = n_groups``, ``N = ssm_state_size``; ``H``); ``xBC = silu(conv(xBC) +
  b)``, depthwise and causal over ``conv_kernel`` positions; x (H heads of
  P), B and C (G groups of N); ``Δ = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``; per head h, reading group ``g = h // (H / G)``: ``S_t =
  exp(Δ_t A_h) S_{t-1} + Δ_t x_t B_{t,g}^T`` (``S_0 = 0``), ``y_t = S_t
  C_{t,g} + D_h x_t``, a ``lax.scan`` over positions (no chunks); ``o = q /
  sqrt(mean(q^2) + eps) * w_norm`` with ``q = y * silu(z)``, the mean over
  each group's ``H P / G`` channels; ``W_out o``;
* ``E``, an expert layer: ``s = sigmoid(W_r u)`` over all experts; the top
  k of ``s + b`` chosen; ``g_i = routed_scaling_factor * s_i / sum_chosen
  s_j``; ``sum_chosen g_i E_i(u) + S(u)`` with ``E_i`` and the shared ``S``
  relu² MLPs, ``W_down relu(W_up u)^2``;
* ``*``, attention: ``q = W_q u`` (``num_attention_heads`` of ``head_dim``),
  ``k = W_k u``, ``v = W_v u`` (``num_key_value_heads``), no rotary; causal
  softmax of ``q.k / sqrt(head_dim)``, query head h reading key-value head
  ``h // group``; ``W_o``.

It is given the same share of the deployment as the program: the experts
``first .. first + held - 1`` (``held`` read from the weights' names) and
the rows of the vocabulary held.  A chosen expert that is not held adds
nothing, here as there.  Attention is computed a head and a block of
queries at a time (``reference/granite.py``).  Departures from the family's
modelling code: none in the equations; ``residual_in_fp32`` is moot in
float32.  It takes the program's weights by the names
``models/decoder.py`` gives them and a feed in its layout (``input_ids``,
``labels``: [B, T]).
"""

import math

import jax
import jax.numpy as jnp

from chipbench import manifest as mf

_granite = mf.load_by_name("reference", "granite")


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def scan(x, dt, a, bm, cm, d):
    """The recurrence as written: x [B, T, H, P], dt [B, T, H], a [H], bm
    and cm [B, T, G, N] (head h reads group ``h // (H / G)``), d [H] -> y
    [B, T, H, P]."""
    heads, groups = x.shape[2], bm.shape[2]

    def step(s, inp):
        xt, dtt, bt, ct = inp
        bt, ct = (jnp.repeat(v, heads // groups, axis=1) for v in (bt, ct))
        s = (jnp.exp(dtt * a)[..., None, None] * s
             + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        return s, jnp.einsum("bhpn,bhn->bhp", s, ct)

    s0 = jnp.zeros(x.shape[:1] + x.shape[2:] + bm.shape[-1:], x.dtype)
    _, y = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, bm, cm)))
    return jnp.moveaxis(y, 0, 1) + d[:, None] * x


def mamba_heads(u, w, p, config):
    """The Mamba-2 mixer's output before ``W_out``: [B, T, H P]."""
    b, t, _ = u.shape
    heads, dh = config["mamba_num_heads"], config["mamba_head_dim"]
    groups, n = config["n_groups"], config["ssm_state_size"]
    inner = heads * dh
    proj = u @ w[p + ".in_proj.w"]
    z, xbc = proj[..., :inner], proj[..., inner:2 * inner + 2 * groups * n]
    dt = proj[..., 2 * inner + 2 * groups * n:]
    filt = w[p + ".conv.w"]                                # [C, K]
    k = filt.shape[1]
    padded = jnp.pad(xbc, [(0, 0), (k - 1, 0), (0, 0)])
    conv = sum(padded[:, i:i + t, :] * filt[:, i] for i in range(k))
    if p + ".conv.b" in w:
        conv = conv + w[p + ".conv.b"]
    xbc = jax.nn.silu(conv)
    x = xbc[..., :inner].reshape(b, t, heads, dh)
    bm = xbc[..., inner:inner + groups * n].reshape(b, t, groups, n)
    cm = xbc[..., inner + groups * n:].reshape(b, t, groups, n)
    y = scan(x, jax.nn.softplus(dt + w[p + ".dt_bias"]),
             -jnp.exp(w[p + ".A_log"]), bm, cm, w[p + ".D"])
    q = (y.reshape(b, t, inner) * jax.nn.silu(z)).reshape(
        b, t, groups, inner // groups)
    q = q / jnp.sqrt(jnp.mean(q * q, axis=-1, keepdims=True)
                     + config["layer_norm_epsilon"])
    return q.reshape(b, t, inner) * w[p + ".norm.scale"]


def _relu2_mlp(x, up, down):
    return jnp.square(jax.nn.relu(x @ up)) @ down


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
    """What the held experts (``w[p + '.experts.<e>.*']``, e counted from
    0 for the expert ``first``) give: every held expert over every token,
    times the gate the token gave it (zero where it did not choose it)."""
    idx, g = route(x, w, p, config)
    out = jnp.zeros_like(x)
    e = 0
    while "%s.experts.%d.up" % (p, e) in w:
        q = "%s.experts.%d" % (p, e)
        y = _relu2_mlp(x, w[q + ".up"], w[q + ".down"])
        out = out + jnp.sum(jnp.where(idx == first + e, g, 0.0),
                            axis=-1)[..., None] * y
        e += 1
    return out


def expert_layer(x, w, p, config, first):
    return routed_part(x, w, p, config, first) \
        + _relu2_mlp(x, w[p + ".shared.up.w"], w[p + ".shared.down.w"])


def attention_heads(u, w, p, config):
    """The attention output before ``W_o``: [B, T, heads * head_dim]."""
    b, t, _ = u.shape
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    d = config["head_dim"]
    q = (u @ w[p + ".q.w"]).reshape(b, t, heads, d)
    k = (u @ w[p + ".k.w"]).reshape(b, t, kv, d)
    v = (u @ w[p + ".v.w"]).reshape(b, t, kv, d)
    return _granite._attend(q, k, v, 1.0 / math.sqrt(d)).reshape(
        b, t, heads * d)


def first_expert(config):
    return config.get("deployment", {}).get("first_expert", 0)


def kinds(config):
    return config["hybrid_override_pattern"][:config["num_hidden_layers"]]


def forward(w, feed, config):
    """``{"logits": [B, T, vocabulary held], "loss": the mean next-token
    cross-entropy over every position, "mamba_first": what the first
    Mamba-2 layer's mixer gives before W_out}``; where ``w`` holds what the
    program's first expert layer gave its router (``<layer>.router.x``:
    [N, D]), also ``"route_gates"``, [N, k]: this router's gates for those
    very rows."""
    eps = config["layer_norm_epsilon"]
    x = w["decoder.embed"][feed["input_ids"]]
    out = {}
    for i, kind in enumerate(kinds(config)):
        p = "decoder.layer%d" % i
        u = _rms_norm(x, w[p + ".ln1.scale"], eps)
        if kind == "M":
            heads = mamba_heads(u, w, p + ".mixer", config)
            out.setdefault("mamba_first", heads)
            x = x + heads @ w[p + ".mixer.out_proj.w"]
        elif kind == "E":
            x = x + expert_layer(u, w, p + ".moe", config,
                                 first_expert(config))
        else:
            x = x + attention_heads(u, w, p + ".attn", config) \
                @ w[p + ".attn.o.w"]
    logits = _rms_norm(x, w["decoder.final_norm.scale"], eps) \
        @ w["decoder.head.w"]
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                               feed["labels"][..., None], axis=-1)
    out.update(logits=logits, loss=nll.mean())
    p = "decoder.layer%d.moe" % kinds(config).index("E")
    if p + ".router.x" in w:
        out["route_gates"] = route(w[p + ".router.x"], w, p, config)[1]
    return out


def loss(w, feed, config):
    return forward(w, feed, config)["loss"]
