"""Parameters, operations and bytes of the Mellum 2 share's training step,
from shapes and from the rows the program's own counters show.

A matmul of ``[n, k] x [k, m]`` is ``2 n k m`` operations.  Forward once;
backward twice the forward; what recomputation runs again is not counted
as useful.  Attention is counted by the pairs of query and key under its
mask: ``T (T + 1) / 2`` a head in a full layer, ``W (W + 1) / 2 + (T - W)
W`` in a sliding one (a query sees the last W keys, its own among them).
The routed experts are counted by the rows routed to the experts held here
(about ``top_k * held / total`` = 1 a token), never by ``top_k``: the other
rows are other chips' work.
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
            "w": config["sliding_window"]}


def layers_of(config):
    """``(sliding layers, full layers)`` among the layers held."""
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    sliding = sum(1 for k in kinds if k == "sliding_attention")
    return sliding, len(kinds) - sliding


def parameter_count(config):
    """Parameters held here, by part."""
    w = widths(config)
    d = w["d"]
    parts = {
        "attention_a_layer": 2 * d * w["h"] * w["dh"]
        + 2 * d * w["kv"] * w["dh"],
        "router_a_layer": d * config["deployment"]["num_experts_routed_over"],
        "experts_a_layer": config["num_experts"] * 3 * d * w["f"],
        "norms_a_layer": 2 * d,
        "embedding_and_head": 2 * config["vocab_size"] * d,
        "final_norm": d}
    parts["a_layer"] = sum(parts[k] for k in (
        "attention_a_layer", "router_a_layer", "experts_a_layer",
        "norms_a_layer"))
    parts["total"] = (config["num_hidden_layers"] * parts["a_layer"]
                      + parts["embedding_and_head"] + parts["final_norm"])
    return parts


def expected_rows_per_token(config):
    """Rows a token sends to the experts held here if the router spreads
    evenly: ``top_k * held / total``."""
    return (config["num_experts_per_tok"] * config["num_experts"]
            / config["deployment"]["num_experts_routed_over"])


def pairs_a_head(config, seq_len):
    """Query-key pairs under the mask, a head and sequence: ``(in a
    sliding layer, in a full one)``."""
    w = min(config["sliding_window"], seq_len)
    return (w * (w + 1) // 2 + (seq_len - w) * w,
            seq_len * (seq_len + 1) // 2)


def forward_flops_per_token(config, seq_len, routed_rows=None):
    """Forward operations a token, by part; ``routed_rows`` are the rows
    a token sends to the held experts of one layer."""
    w = widths(config)
    d, layers = w["d"], config["num_hidden_layers"]
    if routed_rows is None:
        routed_rows = expected_rows_per_token(config)
    sliding, full = layers_of(config)
    in_band, causal = pairs_a_head(config, seq_len)
    counts = parameter_count(config)
    parts = {
        "projections": layers * 2 * counts["attention_a_layer"],
        # QK^T and PV: 2 head_dim each, a pair and head
        "scores": (sliding * in_band + full * causal) / seq_len
        * w["h"] * 4 * w["dh"],
        "router": layers * 2 * counts["router_a_layer"],
        "routed_experts": layers * routed_rows * 2 * 3 * d * w["f"],
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


# -- the flash kernels under a band, grouped heads ---------------------------

def flash_flops_per_step(config, traffic):
    """Attention's matmuls, forward and backward, a step: a pair of query
    and key costs ``12 head_dim`` a head (QK^T and PV forward, ``2
    head_dim`` each; dV, dP, dQ, dK backward, twice that), for the pairs
    under the band in a sliding layer and under the diagonal in a full
    one.  Not counted: the scores both backward kernels compute again,
    the region's second forward, a block's pairs outside the mask."""
    w = widths(config)
    sliding, full = layers_of(config)
    in_band, causal = pairs_a_head(config, traffic["seq_len"])
    return (12 * w["dh"] * w["h"] * traffic["batch"]
            * (sliding * in_band + full * causal))


def flash_bytes_per_step(config, traffic, elt_bytes=2):
    """Least HBM traffic of the same: q, o, dO and dQ at the query heads,
    k, v, dK and dV at the key-value heads, each once."""
    w = widths(config)
    elements = 4 * (w["h"] + w["kv"]) * w["dh"]       # a position
    return (config["num_hidden_layers"] * traffic["batch"]
            * traffic["seq_len"] * elements * elt_bytes)


# -- the grouped products over the held experts -------------------------------

def experts_flops_per_step(config, rows_a_step):
    """``rows_a_step``: rows given to the held experts, all layers
    together, a step.  A row passes three products forward (gate, up,
    down: 2 D F each) and each again twice backward (to the rows, to the
    weights)."""
    w = widths(config)
    return rows_a_step * 3 * 3 * 2 * w["d"] * w["f"]


def experts_bytes_per_step(config, rows_a_step, experts_with_rows,
                           elt_bytes=2):
    """Least HBM traffic of the same nine products: each reads its two
    operands and writes its result once.  ``experts_with_rows``: held
    experts, all layers together, that are given a row in a step."""
    w = widths(config)
    rows_io = rows_a_step * (w["d"] + w["f"])
    return 9 * (rows_io + experts_with_rows * w["d"] * w["f"]) * elt_bytes
