"""The kanana-2 decoder through the program's public API:
``models/decoder.py`` ``build_train`` (latent attention, a dropless top-k
expert layer over the experts held here, Adam, bf16 AMP, every layer in
``fluid.layers.recompute()``)."""

import numpy as np


def model_config(config, recompute):
    """``models/decoder.py``'s keys from the configuration file's: the
    file's ``n_routed_experts`` counts the experts held here, the router
    keeps the published count."""
    cfg = {k: config[k] for k in (
        "vocab_size", "hidden_size", "num_hidden_layers",
        "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta",
        "rope_interleave", "rms_norm_eps", "first_k_dense_replace",
        "intermediate_size", "moe_intermediate_size", "n_shared_experts",
        "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob",
        "initializer_range", "router_bias_std", "router_bias_from_batch")}
    share = config["deployment"]
    return dict(cfg, attention="mla", experts_held=config["n_routed_experts"],
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
    Its startup program is never run.  The first expert layer's router is
    held to its float32 on its own: the program leaves the rows each
    router read in the scope as ``<layer>.moe.router.x`` (the very
    numbers), the reference routes those, and the gates are compared
    with nothing upstream between them."""
    import paddle_tpu as fluid
    from paddle_tpu.models import decoder

    with fluid.unique_name.guard():
        main, _, feeds, loss = decoder.build_train(
            dict(model_config(config, False), keep_router_input=True),
            seq_len=traffic["seq_len"], amp=True, train=False)
    ops = main.global_block().ops
    head = [op for op in ops if op.type == "softmax_with_cross_entropy"][0]
    route = [op for op in ops if op.type == "moe_route"][0]
    return {"program": main, "feeds": feeds,
            "fetch": {"logits": head.input("Logits")[0], "loss": loss.name,
                      "route_gates": route.output("Gate")[0]},
            "weights": [v.name for v in main.list_vars()
                        if v.persistable and v.name.startswith("decoder.")]}


def make_pools(config, traffic, rng):
    """``ring`` pools of ``batch + offsets - 1`` rows of ``seq_len`` ids
    drawn from a Zipf law over the vocabulary held (as text is, so a model
    lowers its loss by learning the frequencies); the labels are the ids
    shifted by one, the last position's a further draw."""
    seq, vocab = traffic["seq_len"], config["vocab_size"]
    rows = traffic["batch"] + traffic["offsets"] - 1
    p = 1.0 / np.arange(1, vocab + 1) ** traffic["zipf_exponent"]
    p /= p.sum()
    pools = []
    for _ in range(traffic["ring"]):
        ids = rng.choice(vocab, size=(rows, seq + 1), p=p).astype("int64")
        pools.append({"input_ids": np.ascontiguousarray(ids[:, :-1]),
                      "labels": np.ascontiguousarray(ids[:, 1:])})
    return pools
