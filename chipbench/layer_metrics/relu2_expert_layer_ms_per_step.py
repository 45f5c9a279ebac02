"""Device milliseconds a step of the expert layers, in every pass (forward,
the recompute region's re-run and its backward: ``xplane.program_op``):
the Program-op tags ``moe_route`` (the router), ``moe_experts`` and its
parts (``.dispatch``, ``.products``, ``.combine``; a part traced in a
``custom_vjp``'s backward is named after the op lowered last, ``<op>.
<part>``, and counts by its part's name) and ``moe_shared`` (the shared
expert).  Left out: each layer's RMSNorm and residual add, and
``moe_count_rows`` (the counters).  A trace without these tags gives
nothing to read."""

PARTS = (".dispatch", ".products", ".combine")


def read(ctx):
    tag_s = ctx["trace"]["tag_s"]
    mine = [s for t, s in tag_s.items()
            if t in ("moe_route", "moe_experts", "moe_shared")
            or t.endswith(PARTS)]
    return 1e3 * sum(mine) if mine else None
