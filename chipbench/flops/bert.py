"""Operations and bytes of the BERT training step, from shapes.

``train_flops_per_example``: forward matmuls of the encoder and of the MLM
head over the gathered positions, backward counted as twice the forward,
nothing recomputed counted (the arithmetic ``bench.py`` has as
``model_train_flops_per_token``, kept here so that no edit there can move
``mfu``, and held to numbers worked by hand in ``selftest/``).
"""


def max_pred(seq_len):
    """Masked positions scored per sequence (``models/bert.py``
    ``default_max_pred``)."""
    return int(0.15 * seq_len) + 1


def train_flops_per_token(config, seq_len):
    d, ff = config["hidden_size"], config["intermediate_size"]
    per_layer = (2 * 4 * d * d          # q, k, v, o projections
                 + 2 * 2 * d * ff       # ffn in and out
                 + 2 * 2 * seq_len * d)  # scores and context
    head = 2 * d * config["vocab_size"] * max_pred(seq_len) / seq_len
    return 3 * (config["num_hidden_layers"] * per_layer + head)


def train_flops_per_example(config, traffic):
    return train_flops_per_token(config, traffic["seq_len"]) \
        * traffic["seq_len"]


def fused_ln_bytes_per_step(config, traffic, elt_bytes=2):
    """Bytes one forward and one backward fused dropout+add+LN call must
    move at each site (two per layer and the embedding's), activations in
    bf16: forward reads x and the residual and writes the output and the
    pre-LN sum kept for the backward, plus mean and rstd in f32; backward
    reads the cotangent, the pre-LN sum and the statistics and writes two
    cotangents.  gamma, beta and their gradients are a few KB."""
    n = traffic["batch"] * traffic["seq_len"]
    d = config["hidden_size"]
    sites = 2 * config["num_hidden_layers"] + 1
    fwd = 4 * n * d * elt_bytes + 2 * n * 4
    bwd = 4 * n * d * elt_bytes + 2 * n * 4
    return sites * (fwd + bwd)


def flash_flops_per_step(config, traffic):
    """Matmul operations attention needs for one forward and one backward
    per layer: QK^T and PV forward (2 matmuls), dV, dP, dK, dQ backward (4),
    each 2*T*T*dh per head.  What the kernels compute again (the scores,
    in both backward kernels) is not counted."""
    t = traffic["seq_len"]
    per_head = 6 * 2 * t * t * (config["hidden_size"]
                                // config["num_attention_heads"])
    return (config["num_hidden_layers"] * traffic["batch"]
            * config["num_attention_heads"] * per_head)


def flash_bytes_per_step(config, traffic, elt_bytes=2):
    """Least HBM traffic of the same: forward reads q, k, v and writes o;
    backward reads q, k, v, o, do and writes dq, dk, dv."""
    per_layer = 12 * traffic["batch"] * traffic["seq_len"] \
        * config["hidden_size"] * elt_bytes
    return config["num_hidden_layers"] * per_layer
