"""The least time the grouped products over the held experts could take for
the rows the program's counters show were routed here in the traced
window's own steps (what they read where it closed less what they read
where it opened; the larger of the rows' operations over the bf16 peak and
their bytes over the HBM peak), over the device time of the ``ragged-dot``
kernels in that window: nine useful products a layer and the three the
recompute region runs again."""


def read(ctx):
    spent = sum(s for k, s in ctx["trace"]["kernel_s"].items()
                if "ragged-dot" in k)
    flops = ctx["flops"]
    if not spent or not hasattr(flops, "counted_between"):
        return None
    config = ctx["cell"]["config"]
    counted = ctx["state"]["counters"]
    layers = flops.counted_between(counted["trace"], counted["end"]).values()
    if not layers:
        return None
    rows_a_step = sum(sum(c["rows"]) / c["steps"] for c in layers)
    # an expert that the counters show a row a step or more on average
    # is taken to be given one in every step
    busy = sum(1 for c in layers for r in c["rows"] if r >= c["steps"])
    least = max(
        flops.experts_flops_per_step(config, rows_a_step)
        / ctx["peaks"]["bf16_flops_per_s"],
        flops.experts_bytes_per_step(config, rows_a_step, busy)
        / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / spent
