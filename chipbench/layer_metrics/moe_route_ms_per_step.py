"""Device milliseconds a step under the Program-op tags ``moe_route`` (the
router's scores and top-k) and ``moe_experts.dispatch`` and
``moe_experts.combine`` (sorting the choices, gathering rows to and from the
held experts' buffer): the expert layer's cost beside its products.
``tag_s`` names an op by its innermost ``pd<i>_<tag>`` scope, and where
jax differentiates a recompute region it rewrites each scope to
``transpose(jvp(pd..))``, which that reduction does not match: with every
layer in a region this reads the forward pass alone; the region's re-run and
its backward read under ``recompute_block_grad`` (PERF.md 7)."""

TAGS = ("moe_route", "moe_experts.dispatch", "moe_experts.combine")


def read(ctx):
    tag_s = ctx["trace"]["tag_s"]
    if not any(t in tag_s for t in TAGS):
        return None
    return 1e3 * sum(tag_s.get(t, 0.0) for t in TAGS)
