"""A decoder-only language model built from a configuration dict, through
the public API as ``models/bert.py`` is: pre-norm blocks ``h = x +
Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))`` (or, with a
``hybrid_override_pattern``, layers of one part each, below), a final
RMSNorm and a head.  The configuration's keys are the model's own (the names a
DeepSeek-V3-family ``config.json`` gives them, and for grouped-query
attention those of a ``layer_types`` / ``rope_parameters`` config) plus
what this device holds of it:

* attention, chosen in one place (``_mixer``): ``attention = "gqa"``,
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
* a hybrid of Mamba-2 and attention layers (the keys of a
  ``granitemoehybrid`` config, IBM Granite 4.0-H; Bamba's are the same):
  ``layer_types[i]`` ``mamba`` is a Mamba-2 mixer (``_mamba2``):
  ``[z | xBC | dt] = W_in u`` (widths ``mamba_n_heads * mamba_d_head``,
  that plus ``2 * mamba_n_groups * mamba_d_state``, and
  ``mamba_n_heads``), ``xBC = silu(causal_conv1d(xBC) + b)`` of width
  ``mamba_d_conv`` (a bias where ``mamba_conv_bias``), split into x (heads
  of ``mamba_d_head``), B and C (``mamba_n_groups`` groups of
  ``mamba_d_state``; head h reads group ``h // (heads / groups)``),
  ``y = ssd_scan(x, dt, B, C)`` in chunks of ``mamba_chunk_size``:
  ``Δ = softplus(dt + dt_bias)``, ``A = -exp(A_log)``, per head ``S_t =
  exp(Δ_t A) S_{t-1} + Δ_t x_t B_t^T`` and ``y_t = S_t C_t + D x_t``;
  ``o = rms_norm_gated(y, z)`` over each group's ``width / groups``
  channels (the whole width for one group), ``W_out o``;
  ``attention`` is grouped-query attention as above, with no rotary where
  ``position_embedding_type`` is ``nope``.  The multipliers:
  ``attention_multiplier`` is the softmax scale (else ``1 /
  sqrt(head_dim)``), ``embedding_multiplier`` multiplies the embedding's
  output, ``residual_multiplier`` each layer's two branches before they
  are added, and the logits are divided by ``logits_scaling``.
  ``tie_word_embeddings``: the head is the embedding's matrix,
  transposed.  Each key is absent, and does nothing, in the other
  families' configs.
* layers of one part each (the keys of a ``nemotron_h`` config, NVIDIA
  Nemotron-H / Nemotron 3): ``hybrid_override_pattern[i]`` says what
  layer i is, ``h = x + part(RMSNorm(x))``: ``M`` a Mamba-2 mixer as
  above, ``E`` an expert layer as above, ``*`` grouped-query attention
  (``position_embedding_type`` ``nope``: no rotary).
* ``mlp_hidden_act`` ``relu2``: every MLP and expert is ``E(x) = W_down
  relu(W_up x)^2``, no gate (else SwiGLU);
  ``moe_shared_expert_intermediate_size``, where given, is the shared
  expert's width (else ``n_shared_experts * moe_intermediate_size``).
* ``vocab_size`` is the rows of the embedding and of the head held here.
* ``recompute``: each layer in ``fluid.layers.recompute()``.

Each part is built under ``framework.device_tag`` (``mla_attention``,
or ``swa_attention`` for a sliding and ``gqa_attention`` for a full
grouped-query layer; ``ssm_mixer`` for a Mamba-2 mixer; ``dense_mlp``,
``moe_shared``, ``lm_head``; the router and the experts tag themselves),
so a device profile reads by part, and each expert layer keeps its
counters on the device (``layers.moe_count_rows``).
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


GRANITE_TINY = {
    "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 3,
    "attention": "gqa", "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "layer_types": ["mamba", "attention", "mamba"],
    "position_embedding_type": "nope", "attention_multiplier": 0.0625,
    "mamba_n_heads": 2, "mamba_d_head": 64, "mamba_d_state": 16,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_chunk_size": 16, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "embedding_multiplier": 12.0,
    "residual_multiplier": 0.22, "logits_scaling": 8.0,
    "tie_word_embeddings": True, "rms_norm_eps": 1e-5,
    "first_k_dense_replace": 3, "intermediate_size": 128,
    "initializer_range": 0.02, "recompute": True,
}


# hidden 64; the pattern "MEM*E": Mamba-2 mixers of 4 heads of 32 in two
# groups of B and C (state 16), relu² expert layers (2 of 8 experts held,
# top 2, a shared expert of 48), NoPE attention of 4 query heads on 2
# key-value heads of 16; an untied head
NEMOTRON_TINY = {
    "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 5,
    "hybrid_override_pattern": "MEM*E", "attention": "gqa",
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "position_embedding_type": "nope", "mamba_n_heads": 4,
    "mamba_d_head": 32, "mamba_d_state": 16, "mamba_n_groups": 2,
    "mamba_d_conv": 4, "mamba_chunk_size": 16, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "mlp_hidden_act": "relu2",
    "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 48,
    "n_routed_experts": 8, "experts_held": 2, "first_expert": 0,
    "n_shared_experts": 1, "num_experts_per_tok": 2,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "rms_norm_eps": 1e-5, "initializer_range": 0.02,
    "router_bias_std": 0.01, "router_bias_from_batch": True,
    "first_k_dense_replace": 0, "recompute": True,
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
    dh = cfg["head_dim"]
    if cfg.get("position_embedding_type") == "nope":
        def rotary(t):
            return t
    else:
        rope = cfg["rope_parameters"][kind]
        scale, magnitude = rotary_table(rope, dh)

        def rotary(t):
            return fluid.layers.rotary_embedding(
                t, theta=rope["rope_theta"], interleaved=False,
                frequency_scale=scale, magnitude=magnitude)

    q = rotary(_heads(_linear(x, heads * dh, prefix + ".q", cfg), heads, dh))
    k = rotary(_heads(_linear(x, kv * dh, prefix + ".k", cfg), kv, dh))
    v = _heads(_linear(x, kv * dh, prefix + ".v", cfg), kv, dh)
    ctx = fluid.layers.fused_multihead_attention(
        q, k, v, causal=True,
        scale=cfg.get("attention_multiplier") or 1.0 / math.sqrt(dh),
        window=cfg["sliding_window"] if kind == "sliding_attention"
        else None)
    ctx = fluid.layers.reshape(
        fluid.layers.transpose(ctx, [0, 2, 1, 3]), [0, 0, heads * dh])
    return _linear(ctx, cfg["hidden_size"], prefix + ".o", cfg)


def _mamba2(u, cfg, prefix):
    heads, dh = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    state, inner = cfg["mamba_d_state"], cfg["mamba_n_heads"] * dh
    groups = cfg.get("mamba_n_groups", 1)
    if cfg.get("mamba_proj_bias"):
        raise ValueError("models/decoder.py builds Mamba-2 mixers with no "
                         "projection bias")
    conv = inner + 2 * groups * state
    proj = _linear(u, inner + conv + heads, prefix + ".in_proj", cfg)

    def part(lo, width):
        return fluid.layers.slice(proj, [2], [lo], [lo + width])

    # the filter and bias start as mamba_ssm's do (a depthwise conv's
    # default, U(+-1/sqrt(width))): at initializer_range the scan's part
    # of the mixer's output is 0.03% (PERF.md, PR 38)
    bound = 1.0 / math.sqrt(cfg["mamba_d_conv"])
    xbc = fluid.layers.swish(fluid.layers.causal_conv1d(
        part(inner, conv), cfg["mamba_d_conv"],
        param_attr=fluid.ParamAttr(
            name=prefix + ".conv.w",
            initializer=fluid.initializer.Uniform(-bound, bound)),
        bias_attr=fluid.ParamAttr(
            name=prefix + ".conv.b",
            initializer=fluid.initializer.Uniform(-bound, bound))
        if cfg["mamba_conv_bias"] else False))

    def group_state(lo):
        # B or C: [B, T, N] for one group, else [B, T, G, N]
        v = fluid.layers.slice(xbc, [2], [lo], [lo + groups * state])
        return v if groups == 1 else fluid.layers.reshape(
            v, [0, 0, groups, state])

    y = fluid.layers.ssd_scan(
        fluid.layers.reshape(fluid.layers.slice(xbc, [2], [0], [inner]),
                             [0, 0, heads, dh]),
        part(inner + conv, heads), group_state(inner),
        group_state(inner + groups * state),
        chunk_size=cfg["mamba_chunk_size"],
        a_log_attr=fluid.ParamAttr(name=prefix + ".A_log"),
        d_attr=fluid.ParamAttr(name=prefix + ".D"),
        dt_bias_attr=fluid.ParamAttr(name=prefix + ".dt_bias"))
    o = fluid.layers.rms_norm_gated(
        fluid.layers.reshape(y, [0, 0, inner]), part(0, inner),
        epsilon=cfg["rms_norm_eps"],
        param_attr=fluid.ParamAttr(name=prefix + ".norm.scale"),
        group_size=inner // groups if groups > 1 else None)
    return _linear(o, cfg["hidden_size"], prefix + ".out_proj", cfg)


def _mixer(i, cfg):
    """Layer i's mixer: ``(its device tag, its builder)``."""
    kind = cfg.get("attention", "mla")
    if kind == "mla":
        return "mla_attention", _mla
    if kind == "gqa":
        layer = cfg["layer_types"][i]
        if layer == "mamba":
            return "ssm_mixer", _mamba2
        if layer == "attention":   # granite's name for a full layer
            layer = "full_attention"
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


def _mlp(x, width, prefix, cfg):
    """SwiGLU, or ``W_down relu(W_up x)^2`` where ``mlp_hidden_act`` is
    ``relu2``."""
    if cfg.get("mlp_hidden_act") != "relu2":
        return _swiglu_mlp(x, width, prefix, cfg)
    return _linear(fluid.layers.relu2(_linear(x, width, prefix + ".up", cfg)),
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
        activation="relu2" if cfg.get("mlp_hidden_act") == "relu2"
        else "swiglu",
        param_attr=fluid.ParamAttr(name=prefix + ".experts",
                                   initializer=init))
    out = routed
    if cfg["n_shared_experts"]:
        with device_tag("moe_shared"):
            shared = _mlp(
                x, cfg.get("moe_shared_expert_intermediate_size")
                or cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
                prefix + ".shared", cfg)
            out = fluid.layers.elementwise_add(routed, shared)
    return out, (rows, index), [(bias, u) for u in used]


def _residual(x, branch, cfg):
    """``x + residual_multiplier * branch``."""
    if "residual_multiplier" in cfg:
        branch = fluid.layers.scale(branch, scale=cfg["residual_multiplier"])
    return fluid.layers.elementwise_add(x, branch)


def _one_part_layer(x, i, cfg, train):
    """``h = x + part(RMSNorm(x))``, the part by ``hybrid_override_pattern
    [i]``: ``M`` a Mamba-2 mixer, ``E`` an expert layer, ``*`` attention."""
    prefix = "decoder.layer%d" % i
    kind = cfg["hybrid_override_pattern"][i]
    if kind == "E":
        part, counted, used = _expert_layer(
            _rms_norm(x, prefix + ".ln1", cfg), cfg, prefix + ".moe", train)
        return fluid.layers.elementwise_add(x, part), counted, used
    if kind == "M":
        tag, build, name = "ssm_mixer", _mamba2, ".mixer"
    elif kind == "*":
        tag, name = "gqa_attention", ".attn"
        build = functools.partial(_gqa, kind="full_attention")
    else:
        raise ValueError("models/decoder.py has no layer kind %r in "
                         "hybrid_override_pattern" % kind)
    with device_tag(tag):
        return fluid.layers.elementwise_add(x, build(
            _rms_norm(x, prefix + ".ln1", cfg), cfg, prefix + name)), \
            None, []


def _layer(x, i, cfg, train):
    if "hybrid_override_pattern" in cfg:
        return _one_part_layer(x, i, cfg, train)
    prefix = "decoder.layer%d" % i
    tag, mixer = _mixer(i, cfg)
    with device_tag(tag):
        h = _residual(x, mixer(
            _rms_norm(x, prefix + ".ln1", cfg), cfg,
            prefix + (".mixer" if tag == "ssm_mixer" else ".attn")), cfg)
    normed = _rms_norm(h, prefix + ".ln2", cfg)
    counted, used = None, []
    if i < cfg["first_k_dense_replace"]:
        with device_tag("dense_mlp"):
            ff = _mlp(normed, cfg["intermediate_size"], prefix + ".mlp",
                      cfg)
    else:
        ff, counted, used = _expert_layer(normed, cfg, prefix + ".moe",
                                          train)
    return _residual(h, ff, cfg), counted, used


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
    if "embedding_multiplier" in cfg:
        x = fluid.layers.scale(x, scale=cfg["embedding_multiplier"])
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
            if cfg.get("tie_word_embeddings"):
                logits = fluid.layers.matmul(
                    x, main.global_block().var("decoder.embed"),
                    transpose_y=True)
            else:
                logits = _linear(x, cfg["vocab_size"], "decoder.head", cfg)
            if "logits_scaling" in cfg:
                logits = fluid.layers.scale(
                    logits, scale=1.0 / cfg["logits_scaling"])
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
