"""Parameters, operations and bytes of the Nemotron 3 Nano share's training
step, from shapes and from the rows the program's own counters show.

A matmul of ``[n, k] x [k, m]`` is ``2 n k m`` operations.  Forward once;
backward twice the forward; what recomputation runs again is not counted
as useful.  Attention is counted by the pairs under the causal mask, ``T
(T + 1) / 2`` a head.  The scan is counted at the least work of its
chunked algorithm at ``chunk_size`` L, with B and C once per group (below).
The routed experts are counted by the rows routed to the experts held here
(about ``top_k * held / total`` = 0.375 a token), never by ``top_k``: the
other rows are other chips' work; an expert is two products, up and down
(relu², no gate).
"""

from chipbench import manifest as mf

# the device counters are the expert layer's own, whatever the model
_counters = mf.load_by_name("flops", "kanana")
counted_rows = _counters.counted_rows
counted_between = _counters.counted_between
rows_per_token = _counters.rows_per_token


def widths(config):
    return {"d": config["hidden_size"], "h": config["num_attention_heads"],
            "kv": config["num_key_value_heads"], "dh": config["head_dim"],
            "f": config["moe_intermediate_size"],
            "fs": config["moe_shared_expert_intermediate_size"],
            "sh": config["mamba_num_heads"], "p": config["mamba_head_dim"],
            "g": config["n_groups"], "n": config["ssm_state_size"],
            "k": config["conv_kernel"], "l": config["chunk_size"]}


def layers_of(config):
    """``(Mamba-2 layers, expert layers, attention layers)`` held."""
    kinds = config["hybrid_override_pattern"][:config["num_hidden_layers"]]
    return kinds.count("M"), kinds.count("E"), kinds.count("*")


def parameter_count(config):
    """Parameters held here, by part: every parameter the program makes,
    the norms' scales and the router's correction bias among them."""
    w = widths(config)
    d, inner = w["d"], w["sh"] * w["p"]
    conv = inner + 2 * w["g"] * w["n"]
    total = config["deployment"]["n_routed_experts_routed_over"]
    parts = {
        "in_proj": d * (inner + conv + w["sh"]),
        "conv": conv * w["k"] + (conv if config["use_conv_bias"] else 0),
        "per_head": 3 * w["sh"],                    # A_log, D, dt_bias
        "gated_norm": inner,
        "out_proj": inner * d,
        "routed_experts_a_layer": config["n_routed_experts"] * 2 * d * w["f"],
        "shared_expert_a_layer": 2 * d * w["fs"],
        "router_a_layer": d * total + total,        # W_r and the bias
        "attention_a_layer": 2 * d * w["h"] * w["dh"]
        + 2 * d * w["kv"] * w["dh"],
        "norm_a_layer": d,
        "embedding_and_head": 2 * config["vocab_size"] * d,
        "final_norm": d}
    parts["mamba_layer"] = sum(parts[k] for k in (
        "in_proj", "conv", "per_head", "gated_norm", "out_proj",
        "norm_a_layer"))
    parts["expert_layer"] = sum(parts[k] for k in (
        "routed_experts_a_layer", "shared_expert_a_layer", "router_a_layer",
        "norm_a_layer"))
    parts["attention_layer"] = parts["attention_a_layer"] \
        + parts["norm_a_layer"]
    mamba, experts, attention = layers_of(config)
    parts["total"] = (mamba * parts["mamba_layer"]
                      + experts * parts["expert_layer"]
                      + attention * parts["attention_layer"]
                      + parts["embedding_and_head"] + parts["final_norm"])
    return parts


def expected_rows_per_token(config):
    """Rows a token sends to the experts held here if the router spreads
    evenly: ``top_k * held / total``."""
    return (config["num_experts_per_tok"] * config["n_routed_experts"]
            / config["deployment"]["n_routed_experts_routed_over"])


def ssd_flops_per_token(config):
    """The scan's forward operations a token and layer, at chunk L: the
    within-chunk products over the causal half (``C B^T`` once a group,
    ``2 N`` a pair; its product with ``Δ x`` a head, ``2 P`` a pair), each
    chunk's state (``B^T (Δ x)``) and its contribution to the next chunk's
    output (``C S``), ``2 N P`` each a head and position."""
    w = widths(config)
    pairs = (w["l"] + 1) / 2                     # a position, causal half
    return (w["g"] * pairs * 2 * w["n"]
            + w["sh"] * (pairs * 2 * w["p"] + 2 * 2 * w["n"] * w["p"]))


def forward_flops_per_token(config, seq_len, routed_rows=None):
    """Forward operations a token, by part; ``routed_rows`` are the rows
    a token sends to the held experts of one layer."""
    w = widths(config)
    d = w["d"]
    if routed_rows is None:
        routed_rows = expected_rows_per_token(config)
    counts = parameter_count(config)
    mamba, experts, attention = layers_of(config)
    parts = {
        "mixer_projections": mamba * 2 * (counts["in_proj"]
                                          + counts["out_proj"]),
        "scan": mamba * ssd_flops_per_token(config),
        "router": experts * 2 * d
        * config["deployment"]["n_routed_experts_routed_over"],
        "routed_experts": experts * routed_rows * 2 * 2 * d * w["f"],
        "shared_expert": experts * 2 * counts["shared_expert_a_layer"],
        "attention_projections": attention * 2
        * counts["attention_a_layer"],
        # QK^T and PV: 2 head_dim each, a pair and head
        "scores": attention * (seq_len + 1) / 2 * w["h"] * 4 * w["dh"],
        "head": 2 * d * config["vocab_size"]}
    parts["total"] = sum(parts.values())
    return parts


def train_flops_per_example(config, traffic, routed_rows):
    """``routed_rows``: the rows a token sends to the held experts of one
    layer; what the window's counters show (``rows_per_token``), never a
    default."""
    seq = traffic["seq_len"]
    return 3 * seq * forward_flops_per_token(
        config, seq, routed_rows)["total"]


# -- the scan's kernels, B and C per group ------------------------------------

def ssd_flops_per_step(config, traffic):
    """The scan's operations a step, forward and backward (twice the
    forward), every Mamba-2 layer held."""
    tokens = traffic["seq_len"] * traffic["batch"]
    return 3 * tokens * layers_of(config)[0] * ssd_flops_per_token(config)


def ssd_bytes_per_step(config, traffic, elt_bytes=2):
    """Least HBM traffic of the same: each pass reads or writes x, Δ
    (float32), B and C (once a group) and y (or their gradients) once a
    position, the backward counted as two passes.  Not counted: the
    chunks' states the forward leaves for the backward, which an
    implementation that runs the forward again need not keep."""
    w = widths(config)
    a_position = ((2 * w["sh"] * w["p"] + 2 * w["g"] * w["n"]) * elt_bytes
                  + 4 * w["sh"])
    tokens = traffic["seq_len"] * traffic["batch"]
    return 3 * tokens * layers_of(config)[0] * a_position


# -- the grouped products over the held relu² experts -------------------------

def experts_flops_per_step(config, rows_a_step):
    """``rows_a_step``: rows given to the held experts, all expert layers
    together, a step.  A row passes two products forward (up and down: 2
    D F each) and each again twice backward (to the rows, to the
    weights): six."""
    w = widths(config)
    return rows_a_step * 2 * 3 * 2 * w["d"] * w["f"]


def experts_bytes_per_step(config, rows_a_step, experts_with_rows,
                           elt_bytes=2):
    """Least HBM traffic of the same six products: each reads its two
    operands and writes its result once.  ``experts_with_rows``: held
    experts, all expert layers together, that are given a row in a step;
    an expert without one is skipped, weights and all."""
    w = widths(config)
    rows_io = rows_a_step * (w["d"] + w["f"])
    return 6 * (rows_io + experts_with_rows * w["d"] * w["f"]) * elt_bytes
