"""Device milliseconds a step under the Program-op tags ``moe_route`` (the
router's scores and top-k) and ``moe_experts.dispatch`` and
``moe_experts.combine`` (sorting the choices, gathering rows to and from the
held experts' buffer): the expert layer's cost beside its products, **in
every pass**.  ``tag_s`` names an op by the innermost ``pd<i>_<tag>`` scope
of its ``op_name``, through the ``jvp(..)`` and ``transpose(jvp(..))`` that
jax puts around a scope inside a region it differentiates
(``xplane.program_op``), so with every layer under ``recompute()`` this is
the forward pass, the region's re-run and its backward together; until
PR 35 it read the forward alone (19.46 ms of the kanana step, PERF.md 6).
A ``while`` that holds the layer's loop is left out and its body's events
count (``xplane.containers``)."""

TAGS = ("moe_route", "moe_experts.dispatch", "moe_experts.combine")


def read(ctx):
    tag_s = ctx["trace"]["tag_s"]
    if not any(t in tag_s for t in TAGS):
        return None
    return 1e3 * sum(tag_s.get(t, 0.0) for t in TAGS)
