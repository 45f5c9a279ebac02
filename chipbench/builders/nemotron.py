"""The Nemotron 3 Nano decoder through the program's public API:
``models/decoder.py`` ``build_train`` with a ``hybrid_override_pattern``
(layers of one part each: Mamba-2 mixers with eight groups of B and C
through the chunked scan kernels, dropless top-k expert layers of relu²
experts beside a shared one over the experts held here, grouped-query
attention with no positional encoding; an untied head, Adam, bf16 AMP,
every layer in ``fluid.layers.recompute()``)."""

from chipbench import manifest as mf

# models/decoder.py's key: the configuration file's (the source config's
# own name)
_RENAMED = {
    "mamba_n_heads": "mamba_num_heads", "mamba_d_head": "mamba_head_dim",
    "mamba_d_state": "ssm_state_size", "mamba_n_groups": "n_groups",
    "mamba_d_conv": "conv_kernel", "mamba_chunk_size": "chunk_size",
    "mamba_conv_bias": "use_conv_bias",
    "rms_norm_eps": "layer_norm_epsilon"}


def model_config(config, recompute):
    """``models/decoder.py``'s keys from the configuration file's: the
    file's ``n_routed_experts`` counts the experts held here, the router
    keeps the published count; the pattern of the layers held is the first
    of the published string, whole periods."""
    cfg = {k: config[k] for k in (
        "vocab_size", "hidden_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "mamba_proj_bias", "mlp_hidden_act", "moe_intermediate_size",
        "moe_shared_expert_intermediate_size", "n_shared_experts",
        "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob",
        "tie_word_embeddings", "initializer_range", "router_bias_std",
        "router_bias_from_batch")}
    cfg.update({ours: config[theirs] for ours, theirs in _RENAMED.items()})
    share = config["deployment"]
    return dict(
        cfg, attention="gqa", position_embedding_type="nope",
        hybrid_override_pattern=config["hybrid_override_pattern"][
            :config["num_hidden_layers"]],
        first_k_dense_replace=0, experts_held=config["n_routed_experts"],
        n_routed_experts=share["n_routed_experts_routed_over"],
        first_expert=share["first_expert"], recompute=recompute)


def build(config, program, traffic, seed):
    """Returns ``(startup, step_program, loss, main)``."""
    import paddle_tpu as fluid
    from paddle_tpu.models import decoder

    with fluid.unique_name.guard():
        main, startup, _, loss = decoder.build_train(
            model_config(config, program["recompute"]),
            seq_len=traffic["seq_len"],
            lr=config["training"]["learning_rate"], amp=True, train=True)
    main.random_seed = startup.random_seed = seed
    return startup, main, loss, main


def build_eval(config, program, traffic):
    """The forward alone under the same AMP rewrite, sharing the training
    program's weights by name: what the plain reference is compared with.
    Its startup program is never run.  Fetched beside the logits and the
    loss: the first expert layer's gates (the reference routes the very
    rows the program's router read, left in the scope as
    ``<layer>.moe.router.x``) and what the first Mamba-2 layer's mixer gave
    before ``W_out`` (its grouped gated norm)."""
    import paddle_tpu as fluid
    from paddle_tpu.models import decoder

    cfg = model_config(config, False)
    with fluid.unique_name.guard():
        main, _, feeds, loss = decoder.build_train(
            dict(cfg, keep_router_input=True), seq_len=traffic["seq_len"],
            amp=True, train=False)
    ops = main.global_block().ops
    head = [op for op in ops if op.type == "softmax_with_cross_entropy"][0]
    route = [op for op in ops if op.type == "moe_route"][0]
    name = "decoder.layer%d.mixer.out_proj.w" % cfg[
        "hybrid_override_pattern"].index("M")
    mamba_first = [op.input("X")[0] for op in ops   # the product, or its cast
                   if op.type == "mul"
                   and op.input("Y")[0].split(".cast_")[0] == name][0]
    return {"program": main, "feeds": feeds,
            "fetch": {"logits": head.input("Logits")[0], "loss": loss.name,
                      "route_gates": route.output("Gate")[0],
                      "mamba_first": mamba_first},
            "weights": [v.name for v in main.list_vars()
                        if v.persistable and v.name.startswith("decoder.")]}


# Zipf ids over the vocabulary held, labels the ids shifted by one: the
# same next-token pools whatever the decoder
make_pools = mf.load_by_name("builders", "kanana").make_pools
