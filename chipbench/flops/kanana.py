"""Parameters, operations and bytes of the kanana-2 share's training step,
from shapes and from the rows the program's own counters show.

A matmul of ``[n, k] x [k, m]`` is ``2 n k m`` operations.  Forward once;
backward twice the forward; what recomputation runs again is not counted
as useful.  Causal attention is counted at half the square.  The routed
experts are counted by the rows routed to the experts held here (about
``top_k * held / total`` = 0.75 a token), never by ``top_k``: the other
rows are other chips' work.
"""


def widths(config):
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    return {"d": config["hidden_size"], "h": config["num_attention_heads"],
            "dqk": nope + rope, "dv": config["v_head_dim"],
            "rank": config["kv_lora_rank"], "rope": rope, "nope": nope,
            "f": config["moe_intermediate_size"]}


def parameter_count(config):
    """Parameters held here, by part (norm scales left out: a few
    thousand)."""
    w = widths(config)
    d, h = w["d"], w["h"]
    attention = (d * h * w["dqk"] + d * (w["rank"] + w["rope"])
                 + w["rank"] * h * (w["nope"] + w["dv"]) + h * w["dv"] * d)
    dense = config["first_k_dense_replace"]
    expert_layers = config["num_hidden_layers"] - dense
    routed = config["n_routed_experts"] * 3 * d * w["f"]
    shared = 3 * d * config["n_shared_experts"] * w["f"]
    router = d * config["deployment"]["n_routed_experts_routed_over"]
    parts = {
        "attention_a_layer": attention,
        "dense_layer": attention + 3 * d * config["intermediate_size"],
        "expert_layer": attention + shared + router + routed,
        "embedding_and_head": 2 * config["vocab_size"] * d}
    parts["total"] = (dense * parts["dense_layer"]
                      + expert_layers * parts["expert_layer"]
                      + parts["embedding_and_head"])
    return parts


def expected_rows_per_token(config):
    """Rows a token sends to the experts held here if the router spreads
    evenly: ``top_k * held / total``."""
    return (config["num_experts_per_tok"] * config["n_routed_experts"]
            / config["deployment"]["n_routed_experts_routed_over"])


def counted_rows(config):
    """``{layer: {"rows": [..], "possible": n, "moved": n, "steps": n}}``
    from the program's device counters (``observability.runtime.
    publish_moe_counters``: one host sync a layer), the totals of the run
    so far; {} where the program keeps none.  The loop reads them where
    its windows open and close (``traffic/train_loop.py`` ``measure``)."""
    try:
        from paddle_tpu.observability.runtime import publish_moe_counters
    except ImportError:
        return {}
    return publish_moe_counters()


def counted_between(before, after):
    """What two readings of ``counted_rows`` lie apart, layer by layer:
    the rows, choices and steps of the window between them.  A layer that
    ``before`` lacks counts from nought; one without a step in between is
    left out."""
    out = {}
    for layer, now in after.items():
        then = before.get(layer)
        if then is not None:
            now = {"rows": [a - b for a, b in zip(now["rows"],
                                                  then["rows"])],
                   **{k: now[k] - then[k] for k in now if k != "rows"}}
        if now["steps"]:
            out[layer] = now
    return out


def rows_per_token(config, counted):
    """Rows a token sent to the experts held here, a layer, over the steps
    that ``counted`` holds (``counted_between``).  Without counters there
    is nothing to count the routed experts' work by, and that is an error:
    the even spread is the caller's to pass where it is meant."""
    layers = counted.values()
    if not layers:
        raise RuntimeError(
            "the program keeps no moe_count_rows counters: the routed "
            "experts' operations cannot be counted")
    return (config["num_experts_per_tok"] * sum(sum(c["rows"]) for c in layers)
            / sum(c["possible"] for c in layers))


def forward_flops_per_token(config, seq_len, routed_rows=None):
    """Forward operations a token, by part; ``routed_rows`` are the rows
    a token sends to the held experts of one layer."""
    w = widths(config)
    d, f = w["d"], w["f"]
    if routed_rows is None:
        routed_rows = expected_rows_per_token(config)
    dense = config["first_k_dense_replace"]
    expert_layers = config["num_hidden_layers"] - dense
    a_layer = {
        "mla_projections": 2 * parameter_count(config)["attention_a_layer"],
        "causal_scores": 2 * seq_len * w["h"] * (w["dqk"] + w["dv"]) / 2}
    parts = {
        "mla_projections": config["num_hidden_layers"]
        * a_layer["mla_projections"],
        "causal_scores": config["num_hidden_layers"]
        * a_layer["causal_scores"],
        "dense_mlp": dense * 2 * 3 * d * config["intermediate_size"],
        "shared_experts": expert_layers * 2 * 3 * d
        * config["n_shared_experts"] * f,
        "router": expert_layers * 2 * d
        * config["deployment"]["n_routed_experts_routed_over"],
        "routed_experts": expert_layers * routed_rows * 2 * 3 * d * f,
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


# -- the flash kernels at d_qk != d_v ----------------------------------------

def flash_flops_per_step(config, traffic):
    """Causal attention's matmuls, forward and backward, a step: QK^T and
    dK, dQ contract or produce ``d_qk``, PV and dV, dP ``d_v``; each is
    ``2 T T d`` a head at the full square, half of it under the causal
    mask.  Forward (d_qk + d_v), backward 2 (d_qk + d_v).  Not counted:
    the scores both backward kernels compute again, the region's second
    forward."""
    w, t = widths(config), traffic["seq_len"]
    per_head = 3 * (w["dqk"] + w["dv"]) * 2 * t * t / 2
    return (config["num_hidden_layers"] * traffic["batch"] * w["h"]
            * per_head)


def flash_bytes_per_step(config, traffic, elt_bytes=2):
    """Least HBM traffic of the same: forward reads q, k, v and writes o;
    backward reads q, k, v, o, do and writes dq, dk, dv."""
    w = widths(config)
    elements = 6 * w["dqk"] + 6 * w["dv"]      # a head and position
    return (config["num_hidden_layers"] * traffic["batch"] * w["h"]
            * traffic["seq_len"] * elements * elt_bytes)


# -- the grouped products over the held experts -------------------------------

def experts_flops_per_step(config, rows_a_step):
    """``rows_a_step``: rows given to the held experts, all expert layers
    together, a step.  A row passes three products forward (gate, up,
    down: 2 D F each) and each again twice backward (to the rows, to the
    weights)."""
    w = widths(config)
    return rows_a_step * 3 * 3 * 2 * w["d"] * w["f"]


def experts_bytes_per_step(config, rows_a_step, experts_with_rows,
                           elt_bytes=2):
    """Least HBM traffic of the same nine products: each reads its two
    operands and writes its result once.  ``experts_with_rows``: held
    experts, all expert layers together, that are given a row in a step;
    an expert without one is skipped, weights and all."""
    w = widths(config)
    rows_io = rows_a_step * (w["d"] + w["f"])
    return 9 * (rows_io + experts_with_rows * w["d"] * w["f"]) * elt_bytes
