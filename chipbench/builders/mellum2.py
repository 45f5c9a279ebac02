"""The Mellum 2 decoder through the program's public API:
``models/decoder.py`` ``build_train`` (grouped-query attention, three
sliding layers to a full one with its own rotary table, a dropless softmax
top-k expert layer over the experts held here, Adam, bf16 AMP, every layer
in ``fluid.layers.recompute()``)."""

from chipbench import manifest as mf


def model_config(config, recompute):
    """``models/decoder.py``'s keys from the configuration file's (the
    source config's own names): the file's ``num_experts`` counts the
    experts held here, the router keeps the published count; the
    ``layer_types`` of the layers held are the first of the published
    list, whole periods."""
    cfg = {k: config[k] for k in (
        "vocab_size", "hidden_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "sliding_window", "rope_parameters", "rms_norm_eps",
        "moe_intermediate_size", "num_experts_per_tok", "norm_topk_prob",
        "initializer_range", "router_bias_std", "router_bias_from_batch")}
    share = config["deployment"]
    return dict(
        cfg, attention="gqa",
        layer_types=config["layer_types"][:config["num_hidden_layers"]],
        first_k_dense_replace=0, n_shared_experts=0,
        scoring_func="softmax", routed_scaling_factor=1.0,
        experts_held=config["num_experts"],
        n_routed_experts=share["num_experts_routed_over"],
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
    ``<layer>.moe.router.x``), and what the first sliding and the first
    full layer's attention gave before ``W_o``."""
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

    def before_w_o(layer):
        name = "decoder.layer%d.attn.o.w" % layer
        return [op.input("X")[0] for op in ops     # W_o, or its AMP cast
                if op.type == "mul"
                and op.input("Y")[0].split(".cast_")[0] == name][0]

    kinds = cfg["layer_types"]
    return {"program": main, "feeds": feeds,
            "fetch": {"logits": head.input("Logits")[0], "loss": loss.name,
                      "route_gates": route.output("Gate")[0],
                      "attn_sliding": before_w_o(
                          kinds.index("sliding_attention")),
                      "attn_full": before_w_o(kinds.index("full_attention"))},
            "weights": [v.name for v in main.list_vars()
                        if v.persistable and v.name.startswith("decoder.")]}


# Zipf ids over the vocabulary held, labels the ids shifted by one: the
# same next-token pools whatever the decoder
make_pools = mf.load_by_name("builders", "kanana").make_pools
