"""Device milliseconds a step under the Program-op tag ``gqa_attention``
(the full layers' grouped-query attention: projections, the YaRN rotary
table, the three flash kernels up to the diagonal, ``W_o``, the residual
add), in every pass: forward, the recompute region's re-run and its
backward (``xplane.program_op``)."""


def read(ctx):
    spent = ctx["trace"]["tag_s"].get("gqa_attention")
    return None if spent is None else 1e3 * spent
