"""A decoder-only language model built from a configuration dict, through
the public API as ``models/bert.py`` is: pre-norm blocks ``h = x +
Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``, a final RMSNorm and an
untied head.  The configuration's keys are the model's own (the names a
DeepSeek-V3-family ``config.json`` gives them, and for grouped-query
attention those of a ``layer_types`` / ``rope_parameters`` config) plus
what this device holds of it:

* attention, chosen in one place (``_attention``): ``attention = "gqa"``,
  grouped-query heads: ``q = W_q x`` as ``num_attention_heads`` heads of
  ``head_dim``, ``k = W_k x`` and ``v = W_v x`` as
  ``num_key_value_heads``, rotary on the whole head in halves (pair
  ``(i, i + head_dim / 2)``), causal attention in which query head h
  reads key-value head ``h // group`` (``fused_multihead_attention``: K
  and V stay ``num_key_value_heads`` wide all the way into the flash
  kernels).  ``layer_types[i]`` says which kind layer i is:
  ``sliding_attention`` sees the last ``sliding_window`` keys and turns
  by ``rope_parameters["sliding_attention"]``, ``full_attention`` sees
  all and turns by ``rope_parameters["full_attention"]`` (``rope_type``
  ``default``, or ``yarn``: ``rotary_table``).
  ``attention = "mla"`` (the default), latent attention.  ``q = W_q x`` as
  heads of ``[q_nope | q_rope]``; ``[c | k_rope] = W_kva x`` with ``c`` of
  ``kv_lora_rank`` and ONE ``k_rope`` shared by all heads; ``[k_nope | v]``
  a head ``= W_kvb RMSNorm(c)``; rotary on ``q_rope`` and ``k_rope``;
  causal attention over ``k = [k_nope | k_rope]`` with a value head
  narrower than the query-key head (``fused_multihead_attention``: the
  flash kernels at ``d_qk != d_v``).  ``q_lora_rank`` must be null.
* the first ``first_k_dense_replace`` layers carry a SwiGLU MLP of
  ``intermediate_size``; the others an expert layer: a router over all
  ``n_routed_experts`` (``layers.moe_route``: sigmoid scores, top
  ``num_experts_per_tok`` by score + correction bias, gates normalised and
  scaled by ``routed_scaling_factor``; with ``router_bias_from_batch`` a
  training step's correction bias is minus each expert's mean score over
  the step's tokens (under softmax scores, minus the log-score that the
  top ``k / E`` of them give it more than), kept for test mode; with ``keep_router_input`` a
  test-mode program leaves what each router read in ``<layer>.moe.
  router.x``), the part of the result that the
  ``experts_held`` experts from ``first_expert`` on give
  (``layers.moe_experts``, dropless), and ``n_shared_experts`` shared
  experts as one SwiGLU MLP of ``n_shared_experts *
  moe_intermediate_size``.  What the absent experts would add is left
  out: under expert parallelism the exchange brings it.
* ``vocab_size`` is the rows of the embedding and of the head held here.
* ``recompute``: each layer in ``fluid.layers.recompute()``.

Each part is built under ``framework.device_tag`` (``mla_attention``,
or ``swa_attention`` for a sliding and ``gqa_attention`` for a full
grouped-query layer; ``dense_mlp``, ``moe_shared``, ``lm_head``; the
router and the experts tag themselves), so a device profile reads by part, and each expert
layer keeps its counters on the device (``layers.moe_count_rows``).
"""

import functools
import math

import paddle_tpu as fluid
from paddle_tpu.framework import device_tag

DECODER_TINY = {
    "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 4, "attention": "mla", "q_lora_rank": None,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "rope_theta": 10000.0, "rope_interleave": True,
    "rms_norm_eps": 1e-6, "first_k_dense_replace": 1,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "n_routed_experts": 8, "experts_held": 2, "first_expert": 0,
    "n_shared_experts": 2, "num_experts_per_tok": 2,
    "routed_scaling_factor": 2.448, "norm_topk_prob": True,
    "initializer_range": 0.02, "router_bias_std": 0.01,
    "router_bias_from_batch": True, "recompute": True,
}


MELLUM_TINY = {
    "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 4,
    "attention": "gqa", "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "sliding_window": 24,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "rope_parameters": {
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 64, "beta_fast": 4,
            "beta_slow": 1, "attention_factor": 1.2772588722239782}},
    "rms_norm_eps": 1e-6, "first_k_dense_replace": 0,
    "moe_intermediate_size": 32, "n_routed_experts": 8, "experts_held": 2,
    "first_expert": 0, "n_shared_experts": 0, "num_experts_per_tok": 2,
    "scoring_func": "softmax", "routed_scaling_factor": 1.0,
    "norm_topk_prob": True, "initializer_range": 0.02,
    "router_bias_std": 0.01, "router_bias_from_batch": True,
    "recompute": True,
}


def _linear(x, size, name, cfg):
    return fluid.layers.fc(
        x, size=size, num_flatten_dims=2, bias_attr=False,
        param_attr=fluid.ParamAttr(
            name=name + ".w", initializer=fluid.initializer.Normal(
                0.0, cfg["initializer_range"])))


def _rms_norm(x, name, cfg):
    return fluid.layers.rms_norm(
        x, epsilon=cfg["rms_norm_eps"],
        param_attr=fluid.ParamAttr(name=name + ".scale"))


def _heads(x, heads, width):
    """[B, T, heads * width] -> [B, heads, T, width]"""
    x = fluid.layers.reshape(x, [0, 0, heads, width])
    return fluid.layers.transpose(x, [0, 2, 1, 3])


def _mla(x, cfg, prefix):
    heads = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    if cfg.get("q_lora_rank") is not None:
        raise ValueError("models/decoder.py builds latent attention with "
                         "a full-rank query only (q_lora_rank null)")

    def rotary(t, offset):
        return fluid.layers.rotary_embedding(
            t, rotary_dim=rope, offset=offset, theta=cfg["rope_theta"],
            interleaved=cfg["rope_interleave"])

    q = rotary(_heads(_linear(x, heads * (nope + rope), prefix + ".q",
                              cfg), heads, nope + rope), nope)
    kva = _linear(x, rank + rope, prefix + ".kva", cfg)
    c = _rms_norm(fluid.layers.slice(kva, [2], [0], [rank]),
                  prefix + ".kv_norm", cfg)
    k_rope = rotary(_heads(fluid.layers.slice(kva, [2], [rank],
                                              [rank + rope]), 1, rope), 0)
    kv = _heads(_linear(c, heads * (nope + dv), prefix + ".kvb", cfg),
                heads, nope + dv)
    k = fluid.layers.concat(
        [fluid.layers.slice(kv, [3], [0], [nope]),
         fluid.layers.expand(k_rope, [1, heads, 1, 1])], axis=3)
    v = fluid.layers.slice(kv, [3], [nope], [nope + dv])
    ctx = fluid.layers.fused_multihead_attention(
        q, k, v, causal=True, scale=1.0 / math.sqrt(nope + rope))
    ctx = fluid.layers.reshape(
        fluid.layers.transpose(ctx, [0, 2, 1, 3]), [0, 0, heads * dv])
    return _linear(ctx, cfg["hidden_size"], prefix + ".o", cfg)


def rotary_table(params, head_dim):
    """``(frequency_scale, magnitude)`` of one ``rope_parameters``
    section for ``layers.rotary_embedding``: None and 1 for ``rope_type``
    ``default``.  ``yarn`` (static, whatever the sequence length): pair i
    of a head turns ``f_i = theta^(-2i/head_dim)`` a position.  ``c(r) =
    head_dim ln(original / (2 pi r)) / (2 ln theta)`` is the pair that
    turns r times over the ``original_max_position_embeddings``; pairs up
    to ``low = floor(c(beta_fast))`` keep ``f_i``, pairs from ``high =
    ceil(c(beta_slow))`` on turn at ``f_i / factor``, those between blend
    linearly, and cos and sin are times ``attention_factor``."""
    kind = params.get("rope_type", "default")
    if kind == "default":
        return None, 1.0
    if kind != "yarn":
        raise ValueError("models/decoder.py has no rope_type %r" % kind)
    pairs = head_dim // 2

    def pair_turning(rotations):
        return (head_dim * math.log(
            params["original_max_position_embeddings"]
            / (2 * math.pi * rotations)) / (2 * math.log(params["rope_theta"])))

    low = max(math.floor(pair_turning(params["beta_fast"])), 0)
    high = min(math.ceil(pair_turning(params["beta_slow"])), pairs - 1)
    ramp = [min(max((i - low) / max(high - low, 0.001), 0.0), 1.0)
            for i in range(pairs)]
    return ([1.0 - r + r / params["factor"] for r in ramp],
            float(params["attention_factor"]))


def _gqa(x, cfg, prefix, kind):
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, rope = cfg["head_dim"], cfg["rope_parameters"][kind]
    scale, magnitude = rotary_table(rope, dh)

    def rotary(t):
        return fluid.layers.rotary_embedding(
            t, theta=rope["rope_theta"], interleaved=False,
            frequency_scale=scale, magnitude=magnitude)

    q = rotary(_heads(_linear(x, heads * dh, prefix + ".q", cfg), heads, dh))
    k = rotary(_heads(_linear(x, kv * dh, prefix + ".k", cfg), kv, dh))
    v = _heads(_linear(x, kv * dh, prefix + ".v", cfg), kv, dh)
    ctx = fluid.layers.fused_multihead_attention(
        q, k, v, causal=True, scale=1.0 / math.sqrt(dh),
        window=cfg["sliding_window"] if kind == "sliding_attention"
        else None)
    ctx = fluid.layers.reshape(
        fluid.layers.transpose(ctx, [0, 2, 1, 3]), [0, 0, heads * dh])
    return _linear(ctx, cfg["hidden_size"], prefix + ".o", cfg)


def _attention(i, cfg):
    """Layer i's attention: ``(its device tag, its builder)``."""
    kind = cfg.get("attention", "mla")
    if kind == "mla":
        return "mla_attention", _mla
    if kind == "gqa":
        layer = cfg["layer_types"][i]
        if layer not in ("sliding_attention", "full_attention"):
            raise ValueError("models/decoder.py has no layer type %r"
                             % layer)
        return ("swa_attention" if layer == "sliding_attention"
                else "gqa_attention"), functools.partial(_gqa, kind=layer)
    raise ValueError("models/decoder.py has no attention kind %r" % kind)


def _swiglu_mlp(x, width, prefix, cfg):
    return _linear(
        fluid.layers.swiglu(_linear(x, width, prefix + ".gate", cfg),
                            _linear(x, width, prefix + ".up", cfg)),
        cfg["hidden_size"], prefix + ".down", cfg)


def _expert_layer(x, cfg, prefix, train):
    """Returns the layer's result, what ``moe_count_rows`` takes, and
    ``(the correction bias's name, the bias the step used)`` where the
    step makes its own."""
    init = fluid.initializer.Normal(0.0, cfg["initializer_range"])
    bias = prefix + ".router.b"
    index, gate, *used = fluid.layers.moe_route(
        x, cfg["n_routed_experts"], cfg["num_experts_per_tok"],
        scale=cfg["routed_scaling_factor"],
        norm_topk_prob=cfg["norm_topk_prob"],
        score_func=cfg.get("scoring_func", "sigmoid"),
        center_bias=train and bool(cfg.get("router_bias_from_batch")),
        keep_input=prefix + ".router.x"
        if cfg.get("keep_router_input") and not train else None,
        param_attr=fluid.ParamAttr(name=prefix + ".router.w",
                                   initializer=init),
        bias_attr=fluid.ParamAttr(
            name=bias, trainable=False,
            initializer=fluid.initializer.Normal(
                0.0, cfg.get("router_bias_std", 0.0))))
    routed, rows = fluid.layers.moe_experts(
        x, index, gate, cfg["moe_intermediate_size"], cfg["experts_held"],
        first_expert=cfg.get("first_expert", 0),
        experts_total=cfg["n_routed_experts"],
        param_attr=fluid.ParamAttr(name=prefix + ".experts",
                                   initializer=init))
    out = routed
    if cfg["n_shared_experts"]:
        with device_tag("moe_shared"):
            shared = _swiglu_mlp(
                x, cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
                prefix + ".shared", cfg)
            out = fluid.layers.elementwise_add(routed, shared)
    return out, (rows, index), [(bias, u) for u in used]


def _layer(x, i, cfg, train):
    prefix = "decoder.layer%d" % i
    tag, attention = _attention(i, cfg)
    with device_tag(tag):
        h = fluid.layers.elementwise_add(
            x, attention(_rms_norm(x, prefix + ".ln1", cfg), cfg,
                         prefix + ".attn"))
    normed = _rms_norm(h, prefix + ".ln2", cfg)
    counted, used = None, []
    if i < cfg["first_k_dense_replace"]:
        with device_tag("dense_mlp"):
            ff = _swiglu_mlp(normed, cfg["intermediate_size"],
                             prefix + ".mlp", cfg)
    else:
        ff, counted, used = _expert_layer(normed, cfg, prefix + ".moe",
                                          train)
    return fluid.layers.elementwise_add(h, ff), counted, used


def decoder(input_ids, cfg, train=True):
    """[B, T] ids -> [B, T, D] hidden states after the final norm.
    ``train``: keep each expert layer's device counters, and route by
    the step's own correction bias where the configuration says so,
    keeping it for test mode."""
    x = fluid.layers.embedding(
        input_ids, size=[cfg["vocab_size"], cfg["hidden_size"]],
        param_attr=fluid.ParamAttr(
            name="decoder.embed", initializer=fluid.initializer.Normal(
                0.0, cfg["initializer_range"])))
    for i in range(cfg["num_hidden_layers"]):
        if cfg.get("recompute"):
            with fluid.layers.recompute():
                x, counted, used = _layer(x, i, cfg, train)
        else:
            x, counted, used = _layer(x, i, cfg, train)
        # outside the region: state, not rematerialised
        if counted is not None and train:
            fluid.layers.moe_count_rows(
                *counted, layer=i, experts_total=cfg["n_routed_experts"])
        for name, bias in used:
            fluid.layers.assign(
                bias, output=x.block.program.global_block().var(name))
    return _rms_norm(x, "decoder.final_norm", cfg)


def build_train(cfg=DECODER_TINY, seq_len=128, lr=1e-4, amp=False,
                train=True):
    """Next-token training program: mean cross-entropy of every position
    against ``labels`` (the ids shifted by one, made by the feeder) over
    the vocabulary held.  Returns ``(main, startup, feed_names, loss)``;
    with ``train=False`` the forward alone (for evaluation: fetch the
    ``softmax_with_cross_entropy`` op's ``Logits``)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        input_ids = fluid.layers.data("input_ids", shape=[seq_len],
                                      dtype="int64")
        labels = fluid.layers.data("labels", shape=[seq_len], dtype="int64")
        x = decoder(input_ids, cfg, train=train)
        with device_tag("lm_head"):
            logits = _linear(x, cfg["vocab_size"], "decoder.head", cfg)
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(
                    logits, fluid.layers.unsqueeze(labels, [2])))
        if train:
            opt = fluid.optimizer.Adam(learning_rate=lr)
            if amp:
                opt = fluid.contrib.mixed_precision.decorate(opt)
            opt.minimize(loss)
        elif amp:
            fluid.contrib.mixed_precision.rewrite_program_bf16(main)
    return main, startup, ["input_ids", "labels"], loss
