"""BERT through the program's public API: ``models/bert.py``
``build_pretrain`` (masked-gather MLM head, tied embeddings, Adam, bf16 AMP)
and, where the cell asks, ``CompiledProgram.with_data_parallel``."""

import numpy as np


def model_config(config, program):
    from paddle_tpu.models import bert

    return bert.BertConfig(
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        layers=config["num_hidden_layers"],
        heads=config["num_attention_heads"],
        ffn=config["intermediate_size"],
        max_seq=config["max_position_embeddings"],
        type_vocab=config["type_vocab_size"],
        dropout=config["hidden_dropout_prob"],
        attn_dropout=config["attention_probs_dropout_prob"],
        fuse_attn=program.get("fuse_attn", "auto"),
        fused_qkv=program.get("fused_qkv", False),
        fused_ln=program.get("fused_ln", False))


def build(config, program, traffic, seed):
    """Returns ``(startup, step_program, loss, main)``: the program the loop
    hands to ``Executor.run`` each step, and the plain main program."""
    import paddle_tpu as fluid
    from paddle_tpu.models import bert

    with fluid.unique_name.guard():
        main, startup, _, loss = bert.build_pretrain(
            model_config(config, program), seq_len=traffic["seq_len"],
            lr=config["training"]["learning_rate"], amp=True, train=True)
    main.random_seed = startup.random_seed = seed
    step = main
    if program.get("data_parallel"):
        step = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name)
    return startup, step, loss, main


def build_eval(config, program, traffic):
    """The forward alone in test mode (no dropout, no optimizer) under the
    same AMP rewrite, sharing the training program's weights by name: what
    the plain reference is compared with.  Its startup program is never
    run."""
    import paddle_tpu as fluid
    from paddle_tpu.models import bert

    with fluid.unique_name.guard():
        main, _, feeds, loss = bert.build_pretrain(
            model_config(config, program), seq_len=traffic["seq_len"],
            amp=True, train=False)
    head = [op for op in main.global_block().ops
            if op.type == "softmax_with_cross_entropy"][0]
    out = {"feeds": feeds,
           "fetch": {"logits": head.input("Logits")[0], "loss": loss.name},
           "weights": [v.name for v in main.list_vars() if v.persistable]}
    if program.get("data_parallel"):
        main = fluid.CompiledProgram(main).with_data_parallel()
    return dict(out, program=main)


def make_pools(config, traffic, rng):
    """``ring`` pools of ``batch + offsets - 1`` rows.  Token ids follow a
    Zipf law over the vocabulary (as text does), so a model can lower its
    loss by learning the frequencies; 15% of the positions are masked and
    their own ids are the labels, in ``build_pretrain``'s gathered layout."""
    from paddle_tpu.models.bert import default_max_pred

    seq, vocab = traffic["seq_len"], config["vocab_size"]
    rows = traffic["batch"] + traffic["offsets"] - 1
    max_pred = default_max_pred(seq)
    n_real = max(1, int(0.15 * seq))
    p = 1.0 / np.arange(1, vocab - 9) ** traffic["zipf_exponent"]
    p /= p.sum()
    weights = np.zeros((rows, max_pred), "float32")
    weights[:, :n_real] = 1.0
    pools = []
    for _ in range(traffic["ring"]):
        ids = 10 + rng.choice(vocab - 10, size=(rows, seq), p=p)
        pos = np.zeros((rows, max_pred), "int64")
        pos[:, :n_real] = np.argsort(rng.random((rows, seq)),
                                     axis=1)[:, :n_real]
        pools.append({
            "input_ids": ids.astype("int64"),
            "token_type_ids": np.zeros((rows, seq), "int64"),
            "attn_mask_bias": np.zeros((rows, 1, 1, seq), "float32"),
            "pos_ids": np.tile(np.arange(seq, dtype="int64"), (rows, 1)),
            "mask_pos": pos,
            "mlm_labels": (np.take_along_axis(ids, pos, axis=1)
                           * (weights > 0)).astype("int64"),
            "mlm_weights": weights,
        })
    return pools
