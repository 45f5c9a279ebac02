"""The least time attention's operations and bytes could take (the larger of
operations over the bf16 peak and bytes over the HBM peak), over the device
time of every flash-attention event (one forward, one dK/dV and one dQ
kernel a site since PR 27).  Recomputation is not counted as useful: the
scores that both backward kernels compute again."""


def read(ctx):
    trace = ctx["trace"]
    spent = sum(s for k, s in trace["kernel_s"].items()
                if "flash_attention_" in k)
    if not spent:
        return None
    cell, peaks, flops = ctx["cell"], ctx["peaks"], ctx["flops"]
    least = max(
        flops.flash_flops_per_step(cell["config"], cell["traffic"])
        / peaks["bf16_flops_per_s"],
        flops.flash_bytes_per_step(cell["config"], cell["traffic"])
        / peaks["hbm_bytes_per_s"])
    return 100.0 * least / spent
